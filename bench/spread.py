#!/usr/bin/env python3
"""Measure a cell's run-to-run spread, for setting its bounds.

    python3 bench/spread.py --workload <cell> --seeds 11,12,13,14,15,16 \\
        --sets 2 --seconds 30 [--traced 21,22,23] [--out runs.jsonl]

Runs ``bench/run.py`` once per seed per set, each in a process of its own
as the benchmark's runs are (the sets repeat the same seeds), then once
per ``--traced`` seed with ``--trace 1``.  Prints each run's result line
and, per set, each end-to-end metric's median and its spread: the distance
between the first and third quartile (``statistics.quantiles(n=4)``) as a
share of the median.  A bound is about five times the widest spread.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    lines = out.stdout.strip().splitlines()
    try:
        res = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        res = {"correct": False, "error": out.stderr[-2000:]}
    res.update(seed=seed, trace=trace, rc=out.returncode,
               stderr=[ln for ln in out.stderr.splitlines() if ln.startswith("bench:")])
    return res


def spread(values: list[float]) -> tuple[float, float]:
    med = statistics.median(values)
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / med if med else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sink = open(args.out, "a") if args.out else None
    runs: list[list[dict]] = []
    for k in range(args.sets):
        runs.append([])
        for seed in seeds:
            res = one(args.workload, seed, args.seconds, 0)
            res["set"] = k
            runs[-1].append(res)
            print(json.dumps(res), flush=True)
            if sink:
                sink.write(json.dumps(res) + "\n")
                sink.flush()
    for seed in [int(s) for s in args.traced.split(",") if s]:
        res = one(args.workload, seed, args.seconds, 1)
        print(json.dumps(res), flush=True)
        if sink:
            sink.write(json.dumps(res) + "\n")
    for k, rs in enumerate(runs):
        names = sorted({m for r in rs for m in r.get("metrics", {})})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in rs if m in r.get("metrics", {})]
            if len(vals) >= 2:
                med, sp = spread(vals)
                print(f"set {k} {m}: median {med!r} spread {sp!r} n {len(vals)} "
                      f"values {vals!r}", flush=True)
        print(f"set {k} correct: {[r.get('correct') for r in rs]}", flush=True)
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
