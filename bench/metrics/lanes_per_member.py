"""Planner and dataplane: lanes the sharded dispatches ran per member group
they advanced -- the sum of ``lanes`` over the sum of ``members`` of the
program's ``repro.hw.launch`` spans in the traced window.  1 is no waste;
above it are inert pad lanes of packed dispatches and the non-member slab
rows a full-width dispatch walks.  Spans without those keys (a
single-group dispatch) count for nothing."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from bench import program_trace  # noqa: E402


def read(r: dict):
    ev = program_trace.spans(program_trace.read(r), program_trace.LAUNCH)
    members = sum(meta.get("members", 0) for *_t, meta in ev)
    if not members:
        return None
    return sum(meta.get("lanes", 0) for *_t, meta in ev) / members
