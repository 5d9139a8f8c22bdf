"""Reduce a profiler trace of the measured window to device numbers.

The traced run writes one ``.xplane.pb``.  Device planes are named
``/device:TPU:<n>``; each holds an ``XLA Ops`` line (one event per device
operation) and an ``XLA Modules`` line (one event per program launch,
named after the jitted function).  The host plane holds the harness's own
``TraceAnnotation`` spans, among them ``bench.window`` around the window.

From these:

* busy time per chip: the union of its operation intervals inside the
  window, and the idle gaps between them;
* device time per program: the launches of each jitted program inside
  the window, by program name (``jit_<function>``);
* the device operations that took most time, each named
  ``<program>/<op>`` by the launch it ran in;
* each idle gap named by the innermost harness span open on the host at
  the gap's midpoint; ``bench.client`` when only the window was open, the
  client's own code between its calls into the program.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "kv.", "svc.", "ctx.", "hw.")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLIENT = "bench.client"


def load(trace_dir: str):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def _events(line):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name)
            for e in line.events]


def planes(pd) -> tuple[list, dict]:
    """``(host_events, {device_plane: {line: events}})`` as plain tuples
    ``(start_ns, end_ns, name)``."""
    host, dev = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev[plane.name] = {ln.name: _events(ln) for ln in plane.lines}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(e for e in _events(ln) if e[2].startswith(SPAN_PREFIXES))
    return host, dev


def short(name: str) -> str:
    """``jit_fused_round(123)`` -> ``jit_fused_round``; an HLO op's text
    ``%fusion.2 = s32[...] fusion(...)`` -> ``fusion.2``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def op_names(ops: list, modules: list) -> list:
    """Each op named ``<program>/<op>`` by the launch it ran inside."""
    mods = sorted(modules)
    starts = [m[0] for m in mods]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = short(mods[i][2]) if i >= 0 and mods[i][1] >= e else "?"
        out.append((s, e, f"{prog}/{short(name)}"))
    return out


def union(intervals: list, lo: float, hi: float) -> list:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out: list = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gaps(gap_list: list, host: list) -> dict:
    """Idle nanoseconds by the innermost host span open at each gap's
    midpoint (spans on one thread nest, so a stack sweep finds it)."""
    # at one instant: starts, then gap midpoints, then ends -- so a span of
    # zero length opens and closes, and never stays open by mistake
    start, mid, end = 0, 1, 2
    marks = [(s, start, i) for i, (s, _e, _n) in enumerate(host)]
    marks += [(e, end, i) for i, (_s, e, _n) in enumerate(host)]
    marks += [((a + b) / 2, mid, j) for j, (a, b) in enumerate(gap_list)]
    marks.sort(key=lambda m: (m[0], m[1]))
    stack: list[int] = []
    out: dict[str, float] = {}
    for _t, what, i in marks:
        if what == start:
            stack.append(i)
        elif what == end:
            if stack and stack[-1] == i:
                stack.pop()
            elif i in stack:
                stack.remove(i)
        else:
            a, b = gap_list[i]
            name = host[stack[-1]][2] if stack else CLIENT
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce(pd, top: int = 10) -> dict:
    """Busy and idle seconds of the window, averaged over chips, device
    seconds by program (summed over chips), the top device operations and
    idle time by host span."""
    host, dev = planes(pd)
    windows = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if not windows or not dev:
        return {}
    lo, hi = windows[-1]
    busy_ns = 0.0
    op_ns: dict[str, float] = {}
    mod_ns: dict[str, float] = {}
    idle: dict[str, float] = {}
    chips = 0
    for lines in dev.values():
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        chips += 1
        merged = union(ops, lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        modules = lines.get(MODULES_LINE, [])
        for s, e, name in op_names(ops, modules):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_ns[name] = op_ns.get(name, 0.0) + d
        for s, e, name in modules:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                mod_ns[short(name)] = mod_ns.get(short(name), 0.0) + d
        spans = [h for h in host if h[2] != WINDOW_SPAN]
        for name, ns in name_gaps(gaps(merged, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + ns
    if not chips:
        return {}
    return {
        "chips": chips,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / chips,
        "modules": {n: ns * 1e-9 for n, ns in mod_ns.items()},
        "device_ops": [[n, ns * 1e-9] for n, ns in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, ns * 1e-9 / chips] for n, ns in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }
