"""Client and KV tier: log entries that ``ReplicatedKV.refresh`` copied to
build the stitched log (snapshot prefix + live log) per acknowledged op --
the sum of ``copied`` over the program's ``repro.kv.refresh`` spans in the
traced window.  It grows with the history while the stitch is O(history)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from bench import program_trace  # noqa: E402


def read(r: dict):
    ev = program_trace.spans(program_trace.read(r), "repro.kv.refresh")
    if not ev or not r.get("acked"):
        return None
    return sum(meta.get("copied", 0) for *_t, meta in ev) / r["acked"]
