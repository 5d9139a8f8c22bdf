"""The program's own spans in the traced window, on the device's clock.

The program marks its host steps with ``repro.<layer>.<step>`` spans
(``src/repro/obs.py``), written to the same profiler trace as the device's
programs.  From the window's ``.xplane.pb`` -- the newest under
``run.TRACE_DIR``, read once per process and file -- this module takes:

* the ``repro.*`` host events that start inside the last ``bench.window``,
  each with its metadata;
* a check of the shared clock.  The n-th ``repro.hw.launch`` of the trace is
  paired with the n-th wire-path program on the device's ``XLA Modules``
  line.  The device program has to start after its launch span starts and
  end before the next ``repro.hw.readback`` span ends, so each pair bounds
  the offset to add to device times.  The offset used is 0 where every pair
  allows it, and otherwise the value nearest 0 that the most pairs allow;
  the pairs that do not allow it are the violations;
* the device's idle time in the window split among the host spans by
  overlap on that clock: each slice of an idle gap goes to the innermost
  ``repro.*`` span open over it, or to ``outside the program``.

The first reading of a trace file prints on stderr the span totals, with
the sum of each numeric metadata key per span name, then the clock check,
the idle table and the long top-level spans.
"""
from __future__ import annotations

import glob
import os
import statistics
import sys

from bench import trace_reduce
from bench.metrics.wirepath_roofline import PROGRAMS
from bench.run import TRACE_DIR

PREFIX = "repro."
LAUNCH = "repro.hw.launch"
READBACK = "repro.hw.readback"
OUTSIDE = "outside the program"
LONG_NS = 50e6

_cache: dict[tuple[str, float], dict] = {}


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------
def read(r: dict | None = None, trace_dir: str = TRACE_DIR) -> dict | None:
    """The analysis of the newest trace under ``trace_dir``, or ``None``
    when there is none.  ``r``, the run's readings, adds the dispatch count
    to what the first reading prints."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        from jax.profiler import ProfileData

        _cache[key] = analyse(ProfileData.from_file(path))
        report(_cache[key], (r or {}).get("dispatches", {}).get("dispatch"))
    return _cache[key]


def host_events(pd) -> tuple[tuple[float, float] | None, list]:
    """The last ``bench.window`` and every ``repro.*`` host event of the
    trace, as ``(start_ns, end_ns, name, metadata)`` sorted by start."""
    windows, events = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s = float(e.start_ns)
                ev = (s, s + float(e.duration_ns), e.name)
                if e.name == trace_reduce.WINDOW_SPAN:
                    windows.append(ev[:2])
                elif e.name.startswith(PREFIX):
                    events.append(ev + ({k: v for k, v in e.stats},))
    events.sort(key=lambda ev: (ev[0], -ev[1]))
    return (windows[-1] if windows else None), events


def wire_modules(modules: list) -> list:
    """The wire-path programs of an ``XLA Modules`` line, by start."""
    return sorted(m for m in modules
                  if any(p in trace_reduce.short(m[2]) for p in PROGRAMS))


# ---------------------------------------------------------------------------
# the shared clock
# ---------------------------------------------------------------------------
def pair_launches(events: list, modules: list) -> list:
    """``(launch_start, device_start, device_end, readback_end)`` for the
    n-th launch span and the n-th wire-path program; ``readback_end`` is the
    end of the first read-back span that starts after the launch ends."""
    launches = [e for e in events if e[2] == LAUNCH]
    backs = [e for e in events if e[2] == READBACK]
    out, j = [], 0
    for (ls, le, *_), (ds, de, _n) in zip(launches, modules):
        while j < len(backs) and backs[j][0] < le:
            j += 1
        out.append((ls, ds, de, backs[j][1] if j < len(backs) else float("inf")))
    return out


def choose_offset(pairs: list) -> dict:
    """The offset interval every pair allows (``lo > hi`` when none is), the
    offset used, and the indices of the pairs that do not allow it."""
    bounds = [(ls - ds, rb - de) for ls, ds, de, rb in pairs]
    if not bounds:
        return {"lo": None, "hi": None, "offset": 0.0, "violations": []}
    lo, hi = max(b[0] for b in bounds), min(b[1] for b in bounds)
    if lo <= hi:
        off = min(max(0.0, lo), hi)
    else:
        off = _most_allowed(bounds)
    bad = [i for i, (a, b) in enumerate(bounds) if not a <= off <= b]
    return {"lo": lo, "hi": hi, "offset": off, "violations": bad}


def _most_allowed(bounds: list) -> float:
    """The value nearest 0 inside the most of the closed ``bounds``."""
    marks = sorted([(a, 0) for a, b in bounds if a <= b]
                   + [(b, 1) for a, b in bounds if a <= b])
    best, best_d, best_x, depth = -1, 0.0, 0.0, 0
    for i, (x, kind) in enumerate(marks):
        if kind == 0:
            depth += 1
            # the run covered at this depth is [x, next mark]
            nxt = marks[i + 1][0] if i + 1 < len(marks) else x
            cand = min(max(0.0, x), nxt)
            if depth > best or (depth == best and abs(cand) < best_d):
                best, best_d, best_x = depth, abs(cand), cand
        else:
            depth -= 1
    return best_x


# ---------------------------------------------------------------------------
# idle time by span
# ---------------------------------------------------------------------------
def innermost(events: list, lo: float, hi: float) -> list:
    """``[lo, hi)`` cut into ``(start, end, name)`` pieces, each named by
    the innermost span open over it (spans of one thread nest) or
    ``OUTSIDE``."""
    marks = []
    for i, (s, e, *_rest) in enumerate(events):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            marks.append((s, 1, -e, i))
            marks.append((e, 0, 0.0, i))
    marks.sort()
    out, stack, t = [], [], lo
    for x, is_start, _, i in marks:
        if x > t:
            out.append((t, x, events[stack[-1]][2] if stack else OUTSIDE))
            t = x
        if is_start:
            stack.append(i)
        elif stack and stack[-1] == i:
            stack.pop()
        elif i in stack:
            stack.remove(i)
    if hi > t:
        out.append((t, hi, OUTSIDE))
    return out


def split_idle(gaps: list, pieces: list) -> dict:
    """Nanoseconds of each gap under each piece's name, by overlap."""
    out: dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            k += 1
    return out


def nesting(events: list) -> list:
    """The index of each event's innermost enclosing event, or ``None``."""
    parent: list = [None] * len(events)
    stack: list[int] = []
    for i, (s, _e, *_rest) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


# ---------------------------------------------------------------------------
# the analysis
# ---------------------------------------------------------------------------
def analyse(pd) -> dict:
    """Window events, the clock check, launch-to-device times, the idle
    split and the long top-level spans of one trace."""
    window, events = host_events(pd)
    _host, dev = trace_reduce.planes(pd)
    chips = {name: lines for name, lines in sorted(dev.items())
             if lines.get(trace_reduce.OPS_LINE)}
    if window is None:
        return {"events": [], "chips": 0}
    lo, hi = window
    first = next(iter(chips.values()), {})
    wire = wire_modules(first.get(trace_reduce.MODULES_LINE, []))
    pairs = pair_launches(events, wire)
    clock = choose_offset(pairs)
    off = clock["offset"]
    inside = [ev for ev in events if lo <= ev[0] < hi]
    pieces = innermost(inside, lo, hi)
    idle: dict[str, float] = {}
    for lines in chips.values():
        busy = trace_reduce.union(lines[trace_reduce.OPS_LINE], lo - off, hi - off)
        gaps = [(a + off, b + off) for a, b in trace_reduce.gaps(busy, lo - off, hi - off)]
        for name, ns in split_idle(gaps, pieces).items():
            idle[name] = idle.get(name, 0.0) + ns / len(chips)
    parent = nesting(inside)
    kids: dict[int, list[int]] = {}
    for i, p in enumerate(parent):
        if p is not None:
            kids.setdefault(p, []).append(i)
    long = [(round((ev[0] - lo) * 1e-9, 3), _chain(inside, kids, i))
            for i, ev in enumerate(inside) if parent[i] is None and ev[1] - ev[0] > LONG_NS]
    return {
        "window": window,
        "chips": len(chips),
        "events": inside,
        "pairs": len(pairs),
        "launches": sum(1 for ev in events if ev[2] == LAUNCH),
        "wire_programs": len(wire),
        "clock": clock,
        "launch_to_device_ns": [ds + off - ls for ls, ds, _de, _rb in pairs if lo <= ls < hi],
        "idle_ns": idle,
        "long": long,
    }


def _chain(events: list, kids: dict, top: int) -> list:
    """``(name, ms)`` of ``events[top]`` and, down from it, of the longest
    child of each: the span that holds the top one's time."""
    path, i = [], top
    while True:
        path.append((events[i][2], round((events[i][1] - events[i][0]) * 1e-6, 3)))
        if i not in kids:
            return path
        i = max(kids[i], key=lambda k: events[k][1] - events[k][0])


def spans(a: dict | None, name: str) -> list:
    """The window's events named ``name``."""
    return [ev for ev in (a or {}).get("events", []) if ev[2] == name]


def totals(a: dict) -> dict:
    """Per span name in the window: ``n``, the count; ``s``, the seconds
    open; and under ``meta`` the sum of each numeric metadata key."""
    out: dict[str, dict] = {}
    for s, e, name, meta in a["events"]:
        t = out.setdefault(name, {"n": 0, "s": 0.0, "meta": {}})
        t["n"] += 1
        t["s"] += (e - s) * 1e-9
        for k, v in meta.items():
            if isinstance(v, (int, float)):
                t["meta"][k] = t["meta"].get(k, 0) + v
    return out


def report(a: dict, dispatches: int | None = None) -> None:
    """The first reading's lines on stderr."""
    tot = sorted(totals(a).items(), key=lambda kv: -kv[1]["s"])
    print("bench: program span totals (count, s, sums of metadata): "
          f"{[[n, t['n'], t['s'], t['meta']] for n, t in tot]}", file=sys.stderr)
    if not a.get("chips"):
        print("bench: no device lines in the traced window", file=sys.stderr)
        return
    c = a["clock"]
    us = [None if v is None else v * 1e-3 for v in (c["lo"], c["hi"])]
    bad = c["violations"]
    print(f"bench: program clock: offset interval [{us[0]}, {us[1]}] us, offset used "
          f"{c['offset'] * 1e-3} us, {a['pairs']} pairs of {a['launches']} launch spans and "
          f"{a['wire_programs']} wire-path programs, {len(bad)} violations "
          f"(first {bad[:5]}); launch spans in the window "
          f"{len(spans(a, LAUNCH))}, dispatch_count delta {dispatches}", file=sys.stderr)
    idle = sorted(a["idle_ns"].items(), key=lambda kv: -kv[1])
    print("bench: device idle by program span: "
          f"{[[n, ns * 1e-9] for n, ns in idle]}", file=sys.stderr)
    if a["launch_to_device_ns"]:
        print("bench: launch to device program, median us, at the offset used: "
              f"{statistics.median(a['launch_to_device_ns']) * 1e-3}", file=sys.stderr)
    print(f"bench: top-level program spans over {LONG_NS * 1e-6:.0f} ms (s into the window, "
          f"longest chain): {a['long'][:20]}", file=sys.stderr)
