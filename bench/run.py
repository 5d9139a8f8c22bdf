#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  The run builds
the deployment, loads and warms it (``setup_s``), measures ``--seconds``,
drains, checks what the window produced against the plain reference, and
prints one JSON line last on standard output.  With ``--trace 0`` it
reports the cell's end-to-end metrics; with ``--trace 1`` it traces the
window and reports the per-layer metrics instead.  Without a TPU, or with
fewer chips than the cell needs, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness, roofline, system, trace_reduce  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def per_layer(cell, res: dict, trace_dir: str) -> tuple[dict, dict | None]:
    """The cell's per-layer metrics from the traced run's readings."""
    r = dict(res["readings"])
    red = trace_reduce.reduce(trace_reduce.load(trace_dir))
    r["trace"] = red
    r["peak"] = roofline.peak(res["device"]["kind"])
    out = {}
    for m in cell.per_layer:
        v = harness.metric_reader(m["name"])(r)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out, red


def report_checks(checks: dict) -> dict:
    """Each number compared, beside its limit; the last lines on stderr."""
    out = {name: {"value": v, "limit": 0} for name, v in checks.items()}
    for name, c in out.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    system.import_program()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    trace_dir = os.path.join(TRACE_DIR, args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                      trace_dir=trace_dir)
    metrics = {m["name"]: {"value": res["metrics"][m["name"]], "unit": m["unit"]}
               for m in cell.end_to_end}
    line = {
        "correct": not any(res["checks"].values()),
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": metrics,
        "device": res["device"],
    }
    if args.trace:
        line["metrics"], red = per_layer(cell, res, trace_dir)
        if red:
            line["device"]["busy_s"] = red["busy_s"]
            line["device"]["window_s"] = red["window_s"]
            line["breakdown"] = {"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiled = res["readings"]["compiled"]
    if compiled:
        print(f"bench: compiled in the window: {compiled}", file=sys.stderr)
    print(f"bench: longest stalls in the window: {res['readings']['stalls']}",
          file=sys.stderr)
    line["checks"] = report_checks(res["checks"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
