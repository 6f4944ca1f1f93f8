#!/usr/bin/env python3
"""Readings for the correctness limit of one cell, in one process: the
program's widest logit gap on each of ``--seeds``, and on the first
``--control`` of them also the float8 control's gap on the same sample.

    python3 bench/calibrate.py --workload <cell> --control 3 --seeds 1 2 3 ...

Each seed is a whole run at the cell's own load and window (the mix's
rate and lead-in, ``run_seconds`` of ``BENCHMARK.json`` unless
``--seconds`` says otherwise).  The control's gap goes through the same
``result_line`` comparison as a run's, in the program's place, and its
``correct`` is printed: it has to come out false.  The limit in
``bench/limits/<cell>.json`` lies above the program's largest reading
and below the control's smallest.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]

    def log(m):
        print(m, file=sys.stderr, flush=True)

    compiles = run.Compiles()
    limit = run.load_limit(args.workload)
    rows = []
    for k, seed in enumerate(args.seeds):
        res = run.serve(args.workload, seed, seconds, False,
                        args.rehearse, control=k < args.control, log=log,
                        compiles=compiles)
        row = {"seed": seed}
        row.update({key: res.get(key) for key in (
            "max_logit_gap", "control_logit_gap", "sample_requests",
            "sample_tokens", "wrong_length", "attempted", "failed",
            "t_reference")})
        line = run.result_line(res, False, args.rehearse, limit)[0]
        row["correct"] = line["correct"]
        row["metrics"] = {k: v["value"] for k, v in line["metrics"].items()}
        row.update({k: res.get(k) for k in (
            "in_flight_start", "in_flight_end", "finished_per_s",
            "tbt_p50_ms", "lateness_p99_ms", "compiles_in_window",
            "memory_peak_bytes")})
        if res["control_logit_gap"] is not None:
            ctl = dict(res, max_logit_gap=res["control_logit_gap"])
            row["control_correct"] = run.result_line(
                ctl, False, args.rehearse, limit)[0]["correct"]
        rows.append(row)
        log(json.dumps(row))
    lower = max((r["max_logit_gap"] for r in rows
                 if r["max_logit_gap"] is not None), default=None)
    ctl = [r["control_logit_gap"] for r in rows
           if r["control_logit_gap"] is not None]
    print(json.dumps({"workload": args.workload, "seconds": seconds,
                      "rows": rows, "lower": lower,
                      "upper": min(ctl) if ctl else None}))


if __name__ == "__main__":
    main()
