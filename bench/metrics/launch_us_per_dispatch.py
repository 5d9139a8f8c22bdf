"""Planner and dataplane: host microseconds per wire-path dispatch from the
dataplane's entry to the jitted call's return (argument conversion,
donation, the launch) -- the mean of the program's ``repro.hw.launch``
spans in the traced window."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from bench import program_trace  # noqa: E402


def read(r: dict):
    ev = program_trace.spans(program_trace.read(r), program_trace.LAUNCH)
    if not ev:
        return None
    return sum(e - s for s, e, *_rest in ev) / len(ev) * 1e-3
