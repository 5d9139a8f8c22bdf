#!/usr/bin/env python3
"""What a program span costs with no profiler session running.

    python3 bench/span_cost.py [--n 1000000]

Every ``--trace 0`` run is in that state, with its backend up, as here.
Prints one JSON line: ns per ``obs.span(name)`` entered and left, the same
with two metadata keywords, one ``obs.enabled()``, and the empty loop they
are measured against; and the backend's platform.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "src"))


def per_call_ns(fn, n: int) -> float:
    """The least of five timings of ``n`` calls, per call."""
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        fn(n)
        best = min(best, time.perf_counter() - t0)
    return best * 1e9 / n


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1_000_000)
    n = ap.parse_args(argv).n
    import jax

    from repro import obs

    platform = jax.devices()[0].platform

    if obs.enabled():
        print("span_cost: a profiler session is running", file=sys.stderr)
        return 2

    def empty(k):
        for _ in range(k):
            pass

    def bare(k):
        for _ in range(k):
            with obs.span("repro.ctx.pump"):
                pass

    def meta(k):
        for i in range(k):
            with obs.span("repro.ctx.retransmit", pending=i, ops=3):
                pass

    def gate(k):
        for _ in range(k):
            obs.enabled()

    loop = per_call_ns(empty, n)
    print(json.dumps({
        "span_ns": per_call_ns(bare, n) - loop,
        "span_meta_ns": per_call_ns(meta, n) - loop,
        "enabled_ns": per_call_ns(gate, n) - loop,
        "loop_ns": loop,
        "n": n,
        "platform": platform,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
