"""Device: microseconds from the start of a wire-path launch on the host to
the start of its program on the chip, median over the window's dispatches
-- each ``repro.hw.launch`` span paired with its ``XLA Modules`` event, on
the clock ``bench.program_trace`` checks and aligns."""
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from bench import program_trace  # noqa: E402


def read(r: dict):
    ns = (program_trace.read(r) or {}).get("launch_to_device_ns")
    if not ns:
        return None
    return statistics.median(ns) * 1e-3
