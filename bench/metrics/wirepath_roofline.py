"""Kernels: the wire path's share of its HBM roofline, in %.

Bytes the window's wire-path dispatches need (``bench.roofline``, from the
dispatch shapes the harness recorded) over the device time of the
wire-path programs in the trace times the chip's peak HBM bandwidth.  The
programs are found by the names of the jitted dispatch functions."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from bench import roofline  # noqa: E402

PROGRAMS = (
    "fused_round",
    "cohort_fused_round",
    "persistent_cohort_rounds",
    "packed_shard_round",
    "sharded",
)


def read(r: dict):
    mods = (r.get("trace") or {}).get("modules", {})
    kernel_s = sum(s for name, s in mods.items() if any(p in name for p in PROGRAMS))
    wire = r.get("wire_dispatches")
    if not kernel_s or not wire:
        return None
    need = roofline.wirepath_bytes(r["config"]["paxos"], wire)
    return 100.0 * need / (kernel_s * r["peak"]["hbm_bytes_per_s"])
