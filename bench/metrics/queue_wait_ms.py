"""Service and context pump: how long an op waited between its submit and
the start of the wire burst that carried it, mean ms -- the sum of
``wait_us`` over the sum of ``ops`` of the program's ``repro.ctx.chunk``
spans in the traced window."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from bench import program_trace  # noqa: E402


def read(r: dict):
    chunks = program_trace.spans(program_trace.read(r), "repro.ctx.chunk")
    ops = sum(meta.get("ops", 0) for *_t, meta in chunks)
    if not ops:
        return None
    return sum(meta.get("wait_us", 0) for *_t, meta in chunks) / ops * 1e-3
