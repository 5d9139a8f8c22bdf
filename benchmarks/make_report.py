"""Render BENCH_wirepath.json as markdown tables.

    PYTHONPATH=src python -m benchmarks.make_report > /tmp/tables.md
"""
from __future__ import annotations

import json
import os

WIREPATH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_wirepath.json")


def wirepath_table() -> None:
    """Render BENCH_wirepath.json (the perf trajectory artifact) as markdown.

    The msgs/s column is what subsequent PRs diff (DESIGN.md §4).
    """
    if not os.path.exists(WIREPATH_JSON):
        return
    with open(WIREPATH_JSON) as f:
        doc = json.load(f)
    meta = doc.get("meta", {})
    print(f"### Wire-path amortization curve (backend={meta.get('backend')}, "
          f"A={meta.get('A')}, N={meta.get('N')})\n")
    print("| path | burst | us/round | msgs/s |")
    print("|---|---|---|---|")
    for r in doc.get("rows", []):
        if "speedup" in r or "burst" not in r:
            continue
        if r.get("skipped"):
            print(f"| {r['path']} | {r['burst']} | — | skipped |")
            continue
        if "msgs_per_s" not in r or "us_per_round" not in r:
            continue
        print(f"| {r['path']} | {r['burst']} | {r['us_per_round']:.0f} "
              f"| {r['msgs_per_s']:,.0f} |")
    speedups = [r for r in doc.get("rows", []) if "speedup" in r]
    if speedups:
        line = ", ".join(f"{r['speedup']:.1f}x @ {r['burst']}" for r in speedups)
        print(f"\nPallas-fused over per-acceptor host loop: {line}")
    print()

    mg = [r for r in doc.get("rows", []) if "groups" in r and "msgs_per_s" in r]
    if mg:
        print(f"### Multi-group aggregate throughput "
              f"(per-group burst={meta.get('MG_BURST')}, "
              f"N={meta.get('MG_N')}; DESIGN.md §5)\n")
        print("| path | G | us/round | aggregate msgs/s |")
        print("|---|---|---|---|")
        for r in mg:
            print(f"| {r['path']} | {r['groups']} | {r['us_per_round']:.0f} "
                  f"| {r['msgs_per_s']:,.0f} |")
        scalings = [r for r in doc.get("rows", []) if "scaling" in r]
        if scalings:
            line = ", ".join(
                f"{r['scaling']:.1f}x ({r['name'].split('/')[1]})"
                for r in scalings
            )
            print(f"\nAggregate scaling G=8 vs G=1: {line}")
        print()

    kv = [r for r in doc.get("rows", []) if "us_per_op" in r]
    if kv:
        print("### Replicated KV tier (DESIGN.md §10)\n")
        print("| path | burst | us/op | ops/s |")
        print("|---|---|---|---|")
        for r in kv:
            print(f"| {r['path']} | {r['burst']} | {r['us_per_op']:.1f} "
                  f"| {r['msgs_per_s']:,.0f} |")
        ratio = next(
            (r for r in doc.get("rows", []) if "kv_ratio" in r), None
        )
        if ratio:
            print(f"\nLeased reads vs write round-trips: "
                  f"{ratio['kv_ratio']:.0f}x cheaper")
        print()


if __name__ == "__main__":
    wirepath_table()
