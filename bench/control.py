"""Read the correctness check's numbers for the program and for its control.

    python3 bench/control.py --workload deepsets-32.drain \\
        --seeds 1,2,3 --seconds 5

For each seed, in one process: build the cell's server, drive the cell's
own traffic for ``--seconds``, then compare every answer with the plain
int8 reference twice: once as served (the program's reading), once with the
reference computed on the int4 grid put in the program's place (the
control's reading). Prints one JSON row per seed. The benchmark's own runs
never run the control; its readings set the upper end of the limits in
PERF.md.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import harness


def int4_answers(kind, cfg, model, pool):
    return kind.reference(cfg, model, pool, int4=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    try:
        for seed in [int(x) % (1 << 64) for x in args.seeds.split(",")]:
            with harness.Session(args.workload, seed) as s:
                win = s.window(s.cell["traffic"], args.seconds, False,
                               np.random.default_rng([seed, 4]))
            program = harness.check(s, win["rec"])
            control = harness.check(s, win["rec"], int4_answers)
            print(json.dumps({
                "seed": seed, "events": win["rec"].n,
                "setup_s": s.setup_s, "warm": s.warmed,
                "program": program["checks"],
                "control": control["checks"],
                "answers_spread": program["answers_spread"],
                "compiles_in_window": win["clock"].compiles}), flush=True)
    except harness.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
