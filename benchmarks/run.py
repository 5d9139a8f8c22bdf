"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run             # all
    PYTHONPATH=src python -m benchmarks.run fig7        # substring filter
"""
from __future__ import annotations

import sys
import time

from . import (
    bench_wirepath,
    fig2_utilization,
    fig7_end_to_end,
    fig7c_bottleneck_shift,
    fig7d_replicated_kv,
    fig8_failure,
    table1_component_latency,
    table2_throughput,
)

SUITES = [
    ("fig2", fig2_utilization),
    ("table1", table1_component_latency),
    ("table2", table2_throughput),
    ("fig7a", fig7_end_to_end),
    ("fig7c", fig7c_bottleneck_shift),
    ("fig7d", fig7d_replicated_kv),
    ("fig8", fig8_failure),
    ("wirepath", bench_wirepath),
]


def main() -> None:
    pat = sys.argv[1] if len(sys.argv) > 1 else ""
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, mod in SUITES:
        if pat and pat not in name:
            continue
        try:
            mod.run()
        except Exception as e:  # keep the harness robust
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}")
    print(f"# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
