"""Planner and dataplane: host microseconds per wire-path dispatch spent in
the blocking read-back of its results (the wait for the device included)
-- the mean of the program's ``repro.hw.readback`` spans in the traced
window."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from bench import program_trace  # noqa: E402


def read(r: dict):
    ev = program_trace.spans(program_trace.read(r), program_trace.READBACK)
    if not ev:
        return None
    return sum(e - s for s, e, *_rest in ev) / len(ev) * 1e-3
