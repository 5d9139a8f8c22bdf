"""The trace reduction, the wire path's byte count and the peak table,
on a small synthetic trace (no chip, no profiler run)."""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import harness, roofline, trace_reduce  # noqa: E402


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*ln) for ln in lines]


class _Trace:
    def __init__(self, planes):
        self.planes = [_Plane(*p) for p in planes]


def synthetic() -> _Trace:
    """A 1000 ns window.  Device ops busy [100, 300) (two overlapping ops)
    and [600, 700); one op straddles the window's start.  The host pumps
    over [50, 350) and waits over [350, 800); a refresh nests in the pump
    over [300, 350)."""
    host = [("python", [
        ("bench.window", 0, 1000),
        ("svc.pump", 50, 300),
        ("kv.refresh", 300, 50),
        ("bench.wait", 350, 450),
        ("PjitFunction(other)", 0, 1000),
    ])]
    dev = [
        ("XLA Modules", [("jit_fused_round(1)", 100, 200), ("jit_copy", 600, 100),
                         ("jit_fused_round(2)", -50, 100)]),
        ("XLA Ops", [("%fusion.1 = s32[8] fusion(%x)", 100, 150),
                     ("tpu_custom_call", 200, 100),
                     ("%copy.2 = s32[8] copy(%y)", 600, 100),
                     ("%fusion.1 = s32[8] fusion(%x)", -50, 100)]),
    ]
    return _Trace([("/host:CPU", host), ("/device:TPU:0", dev),
                   ("/device:TPU:0 SparseCore", [])])


def test_union_and_gaps():
    merged = trace_reduce.union([(5, 9), (0, 3), (2, 4), (20, 30)], 1, 25)
    assert merged == [[1, 4], [5, 9], [20, 25]]
    assert trace_reduce.gaps(merged, 0, 30) == [(0, 1), (4, 5), (9, 20), (25, 30)]


def test_reduce_busy_idle_modules_and_gap_names():
    red = trace_reduce.reduce(synthetic())
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(1000e-9)
    # [0, 50) from the straddling op, [100, 300), [600, 700)
    assert red["busy_s"] == pytest.approx(350e-9)
    mods = red["modules"]
    assert mods == pytest.approx({"jit_fused_round": 250e-9, "jit_copy": 100e-9})
    ops = dict(red["device_ops"])
    assert ops == pytest.approx({"jit_fused_round/fusion.1": 200e-9,
                                 "jit_fused_round/tpu_custom_call": 100e-9,
                                 "jit_copy/copy.2": 100e-9})
    # gaps: [50, 100) mid 75 in the pump; [300, 600) mid 450 in the wait;
    # [700, 1000) mid 850 in the client's own code
    idle = dict(red["idle_gaps"])
    assert idle == pytest.approx({"svc.pump": 50e-9, "bench.wait": 300e-9,
                                  "bench.client": 300e-9})
    assert red["busy_s"] + sum(idle.values()) == pytest.approx(red["window_s"])


def test_short_names():
    assert trace_reduce.short("jit_fused_round(9788040433138826178)") == "jit_fused_round"
    assert trace_reduce.short("%fusion.2 = s32[2048]{0} fusion(%a), kind=kLoop") == "fusion.2"


def test_gap_named_by_innermost_span():
    host = [(0.0, 100.0, "svc.pump"), (40.0, 60.0, "kv.refresh")]
    assert trace_reduce.name_gaps([(45.0, 55.0), (70.0, 80.0)], host) == {
        "kv.refresh": 10.0, "svc.pump": 10.0}


def test_zero_length_span_does_not_stay_open():
    host = [(10.0, 10.0, "kv.refresh"), (0.0, 100.0, "svc.pump"),
            (200.0, 300.0, "bench.wait")]
    got = trace_reduce.name_gaps([(40.0, 60.0), (120.0, 140.0), (240.0, 260.0)], host)
    assert got == {"svc.pump": 20.0, "bench.client": 20.0, "bench.wait": 20.0}


def test_reduce_without_window_or_device_reads_nothing():
    assert trace_reduce.reduce(_Trace([("/host:CPU", [("python", [])])])) == {}


def test_roofline_reader_from_synthetic_trace():
    paxos = {"n_acceptors": 3, "n_instances": 65536, "value_words": 16}
    wire = [(128, 1, (0,)), (8, 1, (120,))]
    r = {"trace": trace_reduce.reduce(synthetic()), "wire_dispatches": wire,
         "config": {"paxos": paxos}, "peak": {"hbm_bytes_per_s": 819e9}}
    need = roofline.wirepath_bytes(paxos, wire)
    got = harness.metric_reader("wirepath_roofline.lat")(r)
    assert got == pytest.approx(100 * need / (250e-9 * 819e9))
    r["trace"] = {}
    assert harness.metric_reader("wirepath_roofline.tput")(r) is None


def test_wirepath_bytes_counts_blocks_lanes_and_rounds():
    paxos = {"n_acceptors": 3, "n_instances": 65536, "value_words": 16}
    slot = 18 * 4
    block = 128 * slot * 4 * 2          # 3 acceptors + learner, read + write
    lane = 17 * 4 + 18 * 4              # burst in + result out
    # one group, 8 lanes inside one block
    assert roofline.wirepath_bytes(paxos, [(8, 1, (0,))]) == block + 8 * lane
    # a window crossing a block boundary visits two blocks
    assert roofline.wirepath_bytes(paxos, [(8, 1, (124,))]) == 2 * block + 8 * lane
    # two groups, a 4-round persistent wave of 128-lane bursts
    assert roofline.wirepath_bytes(paxos, [(128, 4, (0, 512))]) == (
        2 * 4 * block + 2 * 512 * lane)
    # a ring shorter than a block is one block of its own length
    small = {"n_acceptors": 3, "n_instances": 96, "value_words": 16}
    assert roofline.ring_block(96) == 96
    assert roofline.wirepath_bytes(small, [(8, 1, (90,))]) == (
        96 * slot * 4 * 2 + 8 * lane)


def test_peaks_known_and_unknown_device():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peak("TPU v99")
