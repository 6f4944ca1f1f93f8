#!/usr/bin/env python3
"""Knee sweep: serve one cell at a list of offered rates, one after the
other on one cluster, each with the same seed, lead-in and window, and
print a row per rate and the knee.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1 2 3

The knee is the highest offered rate at which the completed rate keeps
up with it (requests finished in the window at 0.9 of the offered rate
or more) and the in-flight count does not grow over the window (by no
more than two standard deviations of a Poisson count, 2 sqrt(n), at
the window's start).  A cell's traffic file takes about four fifths of
it as its rate.
"""
import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402

KEYS = ("attempted", "failed", "finished_per_s", "in_flight_start",
        "in_flight_end", "ttft_p50_s", "ttft_p90_s", "tbt_p50_ms",
        "tbt_p99_ms", "output_tok_per_s", "compiles_in_window",
        "lateness_p99_ms")


def sustained(row: dict) -> bool:
    grow = row["in_flight_end"] - row["in_flight_start"]
    return (row["finished_per_s"] >= 0.9 * row["rate_rps"]
            and grow <= 2.0 * math.sqrt(max(1, row["in_flight_start"])))


def knee(rows) -> float:
    """The highest rate below which every rate was sustained."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate_rps"]):
        if not sustained(row):
            break
        best = row["rate_rps"]
    return best


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    def log(m):
        print(m, file=sys.stderr, flush=True)

    env = run.build(args.workload, args.seed, args.rehearse, False, log=log)
    base = env["mix"]
    rows = []
    for rate in sorted(args.rates):
        env["mix"] = dict(base, arrival=dict(base["arrival"],
                                             rate_rps=rate))
        res = run.drive(env, args.seed, args.seconds)
        row = {"rate_rps": rate}
        row.update({k: res.get(k) for k in KEYS})
        row["sustained"] = sustained(row)
        rows.append(row)
        log(json.dumps(row))
    run.close(env)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "seconds": args.seconds,
                      "lead_in_s": base["lead_in_s"], "rows": rows,
                      "knee_rps": knee(rows)}))


if __name__ == "__main__":
    main()
