"""The program-span reduction: the clock check, the split of idle time by
overlap, and the five readers of program spans, on small synthetic traces
(no chip, no profiler run)."""
from __future__ import annotations

import os
import statistics
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import harness, program_trace, trace_reduce  # noqa: E402


class _Ev:
    def __init__(self, name, start, dur, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats.items())


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e[:3], **(e[3] if len(e) > 3 else {}))
                                        for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*ln) for ln in lines]


class _Trace:
    def __init__(self, planes):
        self.planes = [_Plane(*p) for p in planes]


HOST = [
    ("bench.window", 0, 10000),
    ("svc.pump", 900, 4200),
    ("repro.ctx.pump", 1000, 4000, {"submits": 4, "pending": 0}),
    ("repro.ctx.chunk", 1100, 3700, {"ops": 4, "burst": 4, "wait_us": 8.0}),
    ("repro.ctx.pack", 1100, 200),
    ("repro.hw.launch", 1300, 400, {"blocks": 1}),
    ("repro.hw.readback", 1700, 900),
    ("repro.ctx.deliver", 2600, 2200, {"delivered": 4}),
    ("repro.ctx.retransmit", 4800, 200, {"pending": 0}),
    ("repro.kv.refresh", 5000, 600, {"copied": 30, "applied": 4}),
    ("repro.ctx.pump", 6000, 3000, {"submits": 2, "pending": 0}),
    ("repro.ctx.chunk", 6000, 2000, {"ops": 2, "burst": 2, "wait_us": 2.0}),
    ("repro.hw.launch", 6100, 200, {"blocks": 1}),
    ("repro.hw.readback", 6300, 700),
    ("PjitFunction(fused_round)", 1300, 300),
]


def synthetic(shift: float = 0.0, second: float = 0.0) -> _Trace:
    """A 10 us window with two wire dispatches and a digest.  The device's
    clock reads ``shift`` behind the host's, and the second dispatch's
    program a further ``second`` behind."""
    mods = [("jit_fused_round(1)", 1500 - shift, 500),
            ("jit_tree_digest(7)", 5200 - shift, 100),
            ("jit_fused_round(1)", 6400 - shift - second, 400)]
    ops = [("%fusion.2 = s32[8] fusion(%x)", s, d) for _n, s, d in mods]
    return _Trace([("/host:CPU", [("python", HOST)]),
                   ("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops)])])


def test_host_events_keep_program_spans_with_their_metadata():
    window, events = program_trace.host_events(synthetic())
    assert window == (0.0, 10000.0)
    assert all(name.startswith("repro.") for _s, _e, name, _m in events)
    assert len(events) == 12
    # sorted by start, the enclosing span first
    assert [ev[2] for ev in events[:3]] == ["repro.ctx.pump", "repro.ctx.chunk",
                                            "repro.ctx.pack"]
    assert events[1][3] == {"ops": 4, "burst": 4, "wait_us": 8.0}


@pytest.mark.parametrize("case,pairs,want", [
    # every pair allows 0
    ("consistent", [(0, 100, 200, 300), (1000, 1050, 1100, 1400)],
     {"lo": -50, "hi": 100, "offset": 0.0, "violations": []}),
    # the device clock reads 500-600 ns behind: the bound nearest 0
    ("skewed", [(0, -500, -400, 200), (1000, -600 + 1000, 1100 - 500, 1500)],
     {"lo": 600, "hi": 600, "offset": 600, "violations": []}),
    # no offset suits all: the one that most pairs allow, nearest 0
    ("infeasible", [(0, -1000, -900, 200), (0, -1050, -950, 200), (0, 0, 100, 200)],
     {"lo": 1050, "hi": 100, "offset": 1050, "violations": [2]}),
])
def test_offset_interval(case, pairs, want):
    assert program_trace.choose_offset(pairs) == want, case


def test_no_pairs_keep_the_clock_as_it_is():
    assert program_trace.choose_offset([]) == {"lo": None, "hi": None, "offset": 0.0,
                                               "violations": []}


def test_pairs_skip_other_programs_and_take_the_next_readback():
    _w, events = program_trace.host_events(synthetic())
    dev = trace_reduce.planes(synthetic())[1]["/device:TPU:0"]
    wire = program_trace.wire_modules(dev["XLA Modules"])
    assert [trace_reduce.short(m[2]) for m in wire] == ["jit_fused_round"] * 2
    assert program_trace.pair_launches(events, wire) == [
        (1300.0, 1500.0, 2000.0, 2600.0), (6100.0, 6400.0, 6800.0, 7000.0)]


@pytest.mark.parametrize("shift,offset,to_device", [(0.0, 0.0, 250.0),
                                                    (3000.0, 2800.0, 50.0)])
def test_analysis_aligns_the_device_clock(shift, offset, to_device):
    a = program_trace.analyse(synthetic(shift))
    assert a["clock"]["offset"] == offset
    assert a["clock"]["violations"] == []
    assert a["pairs"] == a["launches"] == a["wire_programs"] == 2
    assert statistics.median(a["launch_to_device_ns"]) == to_device


def test_analysis_reports_a_pair_that_breaks_the_clock():
    a = program_trace.analyse(synthetic(second=1000.0))
    assert a["clock"]["lo"] > a["clock"]["hi"]
    assert len(a["clock"]["violations"]) == 1


def test_idle_split_by_overlap_not_by_midpoint():
    a = program_trace.analyse(synthetic())
    idle = a["idle_ns"]
    assert idle == pytest.approx({
        program_trace.OUTSIDE: 2400, "repro.ctx.pump": 1100, "repro.ctx.pack": 200,
        "repro.hw.launch": 400, "repro.hw.readback": 900, "repro.ctx.deliver": 2200,
        "repro.ctx.retransmit": 200, "repro.kv.refresh": 500, "repro.ctx.chunk": 1100})
    # the entries add up to the window's idle time
    busy = 500 + 100 + 400
    assert sum(idle.values()) == pytest.approx(10000 - busy)
    # no span gets more idle time than it was open
    open_ns: dict[str, float] = {}
    for s, e, name, _m in a["events"]:
        open_ns[name] = open_ns.get(name, 0.0) + e - s
    assert all(ns <= open_ns[name] for name, ns in idle.items()
               if name != program_trace.OUTSIDE)
    # the midpoint naming gives the whole [2000, 5200) gap to the delivery
    spans = [ev[:3] for ev in a["events"]]
    mid = trace_reduce.name_gaps([(2000.0, 5200.0)], spans)
    assert mid == {"repro.ctx.deliver": 3200.0}
    split = program_trace.split_idle([(2000.0, 5200.0)],
                                     program_trace.innermost(a["events"], 0.0, 10000.0))
    assert split == {"repro.hw.readback": 600.0, "repro.ctx.deliver": 2200.0,
                     "repro.ctx.retransmit": 200.0, "repro.kv.refresh": 200.0}


def test_totals_sum_each_metadata_key_per_span_name():
    t = program_trace.totals(program_trace.analyse(synthetic()))
    assert t["repro.ctx.chunk"] == {"n": 2, "s": pytest.approx(5700e-9),
                                    "meta": {"ops": 6, "burst": 6, "wait_us": 10.0}}
    assert t["repro.ctx.pump"]["meta"] == {"submits": 6, "pending": 0}
    assert t["repro.kv.refresh"]["meta"] == {"copied": 30, "applied": 4}
    assert t["repro.ctx.pack"] == {"n": 1, "s": pytest.approx(200e-9), "meta": {}}


def test_innermost_skips_empty_spans_and_clips_to_the_window():
    events = [(-50.0, 40.0, "repro.a", {}), (10.0, 10.0, "repro.b", {}),
              (20.0, 30.0, "repro.c", {}), (90.0, 150.0, "repro.d", {})]
    assert program_trace.innermost(events, 0.0, 100.0) == [
        (0.0, 20.0, "repro.a"), (20.0, 30.0, "repro.c"), (30.0, 40.0, "repro.a"),
        (40.0, 90.0, program_trace.OUTSIDE), (90.0, 100.0, "repro.d")]


def test_long_top_level_spans_name_their_longest_chain(monkeypatch):
    monkeypatch.setattr(program_trace, "LONG_NS", 3500.0)
    a = program_trace.analyse(synthetic())
    [(at, chain)] = a["long"]
    assert at == 0.0
    assert [name for name, _ms in chain] == ["repro.ctx.pump", "repro.ctx.chunk",
                                             "repro.ctx.deliver"]


def test_analysis_without_window_or_chip():
    assert program_trace.analyse(_Trace([("/host:CPU", [("python", [])])])) == {
        "events": [], "chips": 0}
    a = program_trace.analyse(_Trace([("/host:CPU", [("python", HOST)])]))
    assert a["chips"] == 0 and a["pairs"] == 0 and a["idle_ns"] == {}


@pytest.mark.parametrize("name,want", [
    ("queue_wait_ms", 10.0 / 6 * 1e-3),
    ("launch_us_per_dispatch", 0.3),
    ("readback_us_per_dispatch", 0.8),
    ("launch_to_device_us", 0.25),
    ("refresh_copied_per_op", 5.0),
])
def test_readers(monkeypatch, name, want):
    a = program_trace.analyse(synthetic())
    monkeypatch.setattr(program_trace, "read", lambda r=None: a)
    assert harness.metric_reader(name)({"acked": 6}) == pytest.approx(want)
    # a trace without program spans, as the parent commit writes, reads nothing
    bare = program_trace.analyse(_Trace([("/host:CPU", [("python", HOST[:2])])]))
    monkeypatch.setattr(program_trace, "read", lambda r=None: bare)
    assert harness.metric_reader(name)({"acked": 6}) is None
    monkeypatch.setattr(program_trace, "read", lambda r=None: None)
    assert harness.metric_reader(name)({"acked": 6}) is None


def test_read_finds_no_trace(tmp_path):
    assert program_trace.read({}, trace_dir=str(tmp_path)) is None


def test_read_takes_spans_and_metadata_from_a_real_trace(tmp_path, capsys):
    """A profiler session on the CPU: host events only, read once."""
    import jax

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "src"))
    from repro import obs

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            with obs.span("repro.ctx.pump") as sp:
                with obs.span("repro.ctx.retransmit", pending=3):
                    pass
                if obs.enabled():
                    sp.set_metadata(submits=2, pending=0)
    finally:
        jax.profiler.stop_trace()
    a = program_trace.read({"dispatches": {"dispatch": 0}}, trace_dir=str(tmp_path))
    assert [(n, m) for _s, _e, n, m in a["events"]] == [
        ("repro.ctx.pump", {"submits": 2, "pending": 0}),
        ("repro.ctx.retransmit", {"pending": 3})]
    assert a["chips"] == 0
    err = capsys.readouterr().err
    assert "no device lines" in err
    # the totals line reads each span's metadata, summed
    assert "['repro.ctx.pump', 1, " in err and "{'submits': 2, 'pending': 0}" in err
    assert program_trace.read({}, trace_dir=str(tmp_path)) is a
    assert capsys.readouterr().err == ""
