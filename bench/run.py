"""Run one cell of the on-chip serving benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json``; ``harness.py`` says
how a run finds its files. With ``--trace 0`` the result line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window. The last line of standard output is
the result, one JSON object; the last lines of standard error are the
numbers the correctness check compared, each beside its limit. The run's
details (compiles inside the window, the generator's lag, the spread of
the answers) go to earlier lines and to ``bench/.out/``. Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")

    import harness
    try:
        out = harness.run(args.workload, args.seed % (1 << 64), args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    details, result = out["details"], out["result"]
    harness.OUT_DIR.mkdir(parents=True, exist_ok=True)
    path = harness.OUT_DIR / (f"{args.workload}.seed{args.seed}"
                              f".trace{args.trace}.json")
    path.write_text(json.dumps(details, indent=1) + "\n")
    print(f"compiles in window: {details['compiles_in_window']}")
    if "generator_lag_us" in details:
        print(f"generator lag us: {json.dumps(details['generator_lag_us'])}")
    print(f"details: {json.dumps(details)}")
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
