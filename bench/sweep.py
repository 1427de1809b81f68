"""Find a cell's knee: the highest Poisson rate served without a backlog.

    python3 bench/sweep.py --workload deepsets-32.drain --seed 7 \\
        --seconds 5 --rates 2000,5000,10000 --refine 3

Builds the server of the cell's configuration once (any cell of that
configuration will do: its own traffic is not used), then offers each rate for ``--seconds`` and
prints one JSON row per rate: events offered and answered inside the
window, latency p50/p99 from the intended arrival, the generator's lag,
the mean batch, and the median queue wait in the first and the last quarter
of the arrivals (a wait that rises through the window is a growing
backlog). Every answer is also checked against the reference. ``--refine``
then halves the gap between the highest rate sustained and the lowest one
above it that was not, that many times, on a log scale. The last row is the
knee: the highest rate sustained (``sustained``, below).
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import harness

#: A rate is sustained when nearly every event is answered inside the window,
#: the queue wait does not grow from the first quarter of the arrivals to the
#: last, and the generator keeps to its schedule. The generator's lag is
#: read at its 90th percentile: the host pauses for about a tenth of a
#: second some 1% of the time, which sets its 99th at any rate.
MIN_ANSWERED = 0.99
WAIT_GROWTH = 2.0
WAIT_SLACK_US = 500.0
MAX_LAG_P90_US = 2000.0


def sustained(row: dict) -> bool:
    first, last = row["queue_wait_us_p50_first_last_quarter"]
    lag = row["generator_lag_us"]
    return (row["answered_in_window"] >= MIN_ANSWERED * row["offered"]
            and first is not None and last is not None
            and last <= WAIT_GROWTH * first + WAIT_SLACK_US
            and lag["p90"] <= MAX_LAG_P90_US)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated events/s")
    ap.add_argument("--refine", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        session = harness.Session(args.workload, args.seed % (1 << 64))
        with session as s:
            rows = []

            def offer(rate: float) -> dict:
                rng = np.random.default_rng([args.seed % (1 << 64), 5,
                                             int(rate)])
                win = s.window({"generator": "poisson", "rate_eps": rate},
                               args.seconds, False, rng)
                ctx = harness.timings(s, win)
                d = harness.window_details(win)
                row = {
                    "rate_eps": rate, "offered": ctx.attempted,
                    "answered_in_window": ctx.answered_in_window,
                    "latency_p50_us": harness.percentile(ctx.latency_us, 50),
                    "latency_p99_us": harness.percentile(ctx.latency_us, 99),
                    "generator_lag_us": d.get("generator_lag_us"),
                    "mean_batch": d["mean_batch"],
                    "queue_wait_us_p50_first_last_quarter":
                        d.get("queue_wait_us_p50_first_last_quarter"),
                    "compiles_in_window": d["compiles_in_window"],
                    "checks": harness.check(s, win["rec"])["checks"]}
                row["sustained"] = sustained(row)
                rows.append(row)
                print(json.dumps(row), flush=True)
                return row

            for rate in [float(r) for r in args.rates.split(",")]:
                offer(rate)
            for _ in range(args.refine):
                good = [r["rate_eps"] for r in rows if r["sustained"]]
                lo = max(good, default=None)
                bad = [r["rate_eps"] for r in rows
                       if not r["sustained"] and (lo is None
                                                  or r["rate_eps"] > lo)]
                if lo is None or not bad:
                    break
                offer(round(float(np.sqrt(lo * min(bad)))))
            good = [r["rate_eps"] for r in rows if r["sustained"]]
            print(json.dumps({"knee_eps": max(good, default=None)}),
                  flush=True)
    except harness.NoChip as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
