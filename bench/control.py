#!/usr/bin/env python3
"""The control of the correctness check: runs that must come out incorrect.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

Each seed runs the cell as ``run.py`` does, at the cell's own size and
load, except that the client acknowledges every write when it submits it,
before it is decided -- the early acknowledgement a faster client would
be tempted by, which breaks the configuration's guarantee that an
acknowledged write is in the decided log and reads back.  With
``--control 0`` the same runs are made unbroken, for the readings of
sound runs.  One JSON line per seed: the checks' numbers and ``correct``.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness, system  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    system.import_program()
    import gc

    import jax

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    t = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = harness.run(cell, seed, args.seconds, False, t,
                          control=bool(args.control))
        print(json.dumps({
            "seed": seed, "control": args.control,
            "correct": not any(res["checks"].values()),
            "checks": res["checks"], "attempted": res["attempted"],
            "metrics": res["metrics"],
        }), flush=True)
        del res
        gc.collect()
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
