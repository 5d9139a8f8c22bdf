#!/usr/bin/env python3
"""Find a cell's operating point on the chip: the knee of an open loop, or
the plateau of a closed loop.

    python3 bench/sweep.py --workload <cell> --seed <n> --step-seconds 5 \\
        --rates 2000,4000,8000          # open loop: offered ops/s
    python3 bench/sweep.py --workload <cell> --seed <n> --step-seconds 5 \\
        --populations 256,512,1024      # closed loop: outstanding ops

One process builds, loads and warms the cell's deployment once, then runs
each step on it with the cell's own mix at that rate or population, and
prints one JSON line per step: offered and completed ops/s, latency p50
and p99 from due time (open loop) or submission (closed loop), and how
late the generator ran.  The rate or population a cell fixes is read from
these lines once, when the cell is defined; runs of the benchmark never
search.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness, system  # noqa: E402


def step(client, cell, seed: int, seconds: float, rate=None, population=None) -> dict:
    import numpy as np

    mix = dict(cell.traffic)
    if rate is not None:
        mix["rate_per_s"] = rate
    if population is not None:
        mix["population"] = population
    if mix["loop"] == "closed":
        # a new population is a new cohort structure: warm its shapes
        harness.warm_up(client, dataclasses.replace(cell, traffic=mix), seed, 60.0)
    sched = cell.generator.schedule(mix, seed, seconds, client.n_keys)
    c0 = client.compiles
    d0 = client.sys.dispatch_counts()["dispatch"]
    t0 = time.perf_counter()
    if sched.loop == "open":
        first, last = client.run_open(sched, t0, t0 + seconds)
    else:
        first, last = client.run_closed(sched, t0 + seconds)
    t1 = time.perf_counter()
    d1 = client.sys.dispatch_counts()["dispatch"]
    client.drain(30.0)
    ops = range(first, last)
    acks = np.array([client.t_ack[o] for o in ops])
    done = int(np.sum((acks >= t0) & (acks <= t1)))
    out = {"ops_per_s": done / (t1 - t0), "ops": last - first,
           "dispatches": d1 - d0, "unacked": int(np.sum(acks < 0)),
           "compiles": client.compiles - c0}
    if sched.loop == "open":
        lat = np.array([client.t_ack[o] - client.due_times[o] for o in ops
                        if client.t_ack[o] >= 0])
        late = np.array([client.t_issue[o] - client.due_times[o] for o in ops])
        out.update(offered=rate, late_p99_ms=float(np.percentile(late, 99) * 1e3))
    else:
        lat = np.array([client.t_ack[o] - client.t_issue[o] for o in ops
                        if t0 <= client.t_ack[o] <= t1])
        out.update(population=population)
    out["p50_ms"] = float(np.percentile(lat, 50) * 1e3)
    out["p99_ms"] = float(np.percentile(lat, 99) * 1e3)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--step-seconds", type=float, default=5.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--populations", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    system.import_program()
    import jax

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("sweep: needs the cell's TPU chips", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    t = time.perf_counter()
    sysm = system.System(cell.config, cell.chips)
    client = harness.Client(sysm, cell.config, cell.traffic, args.seed)
    jax.monitoring.register_event_duration_secs_listener(harness._count_compiles([client]))
    if client.kv_mode:
        harness.load_kv(client)
    harness.warm_up(client, cell, args.seed, 120.0)
    rates = [float(x) for x in args.rates.split(",") if x]
    pops = [int(x) for x in args.populations.split(",") if x]
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    for k, r in enumerate(rates):
        print(json.dumps(step(client, cell, args.seed + k, args.step_seconds, rate=r)),
              flush=True)
    for k, p in enumerate(pops):
        print(json.dumps(step(client, cell, args.seed + k, args.step_seconds,
                              population=p)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
