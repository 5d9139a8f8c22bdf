"""Program spans (``repro.obs``) on the single-group served path.

A small fused ``PaxosContext`` with snapshots, under ``ReplicatedKV``, is
driven once inside a profiler session: every span of the served path has
to appear in the trace with its metadata, nested as the path nests, and
its counts have to agree with the program's own counters.  Driven again
with no session, the results and the dispatch count are the same.
"""
import glob
import os
import sys

import jax
import pytest

sys.path.insert(0, "src")

from repro import obs  # noqa: E402
from repro.core.api import PaxosContext  # noqa: E402
from repro.core.types import PaxosConfig  # noqa: E402
from repro.serve.engine import ConsensusService  # noqa: E402
from repro.serve.kv import ReplicatedKV  # noqa: E402

CFG = PaxosConfig(n_acceptors=3, n_instances=64, batch=8)

# span -> (its metadata keys, the spans it may sit directly inside)
TABLE = {
    "repro.ctx.pump": ({"submits", "pending"}, {None, "repro.kv.read_index"}),
    "repro.ctx.chunk": ({"ops", "burst", "wait_us"}, {"repro.ctx.pump"}),
    "repro.ctx.pack": (set(), {"repro.ctx.chunk"}),
    "repro.hw.launch": ({"blocks"}, {"repro.ctx.chunk"}),
    "repro.hw.readback": (set(), {"repro.ctx.chunk"}),
    "repro.ctx.deliver": ({"delivered"}, {"repro.ctx.chunk"}),
    "repro.ctx.retransmit": ({"pending"}, {"repro.ctx.pump"}),
    "repro.kv.refresh": ({"copied", "applied"}, {None, "repro.kv.read_index"}),
    "repro.kv.read_index": ({"pumps"}, {None}),
    "repro.snapshot.drain": ({"entries"}, {None}),
    "repro.snapshot.seal": ({"prefix"}, {"repro.snapshot.drain"}),
}


def drive():
    """Puts from three sessions, a leased get and a read-index get per
    step, a snapshot every third step.  Returns the context, the KV tier,
    every get's answer and, per ``refresh`` call, the stitched log's length
    after it."""
    ctx = PaxosContext(CFG, fused=True, snapshots=True)
    svc = ConsensusService(ctx)
    kv = ReplicatedKV(svc)
    refresh, stitched = kv.refresh, []

    def counted():
        refresh()
        stitched.append(len(ctx.full_group_log(0)))

    kv.refresh = counted
    sessions = [kv.session(f"client{i}") for i in range(3)]
    answers = []
    for step in range(10):
        for i, s in enumerate(sessions):
            s.put(b"k%d" % ((step + i) % 5), b"v%d.%d" % (step, i))
        answers.append(sessions[0].get(b"k0"))     # stale lease: read-index
        svc.pump()
        kv.refresh()
        answers.append(sessions[1].get(b"k1"))     # leased
        if step % 3 == 2:
            ctx.snapshot_group(0)
    kv.refresh = refresh
    return ctx, kv, answers, stitched


def _program_events(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    s = float(e.start_ns)
                    out.append((s, s + float(e.duration_ns), e.name, dict(e.stats)))
    return sorted(out, key=lambda ev: (ev[0], -ev[1]))


def _parents(events: list) -> list:
    """The name of each event's innermost enclosing event, or ``None``."""
    out = []
    for i, (s, e, _n, _m) in enumerate(events):
        best = None
        for j, (ps, pe, _pn, _pm) in enumerate(events):
            if j != i and ps <= s and e <= pe and (j < i or (ps, pe) != (s, e)):
                if best is None or pe - ps < events[best][1] - events[best][0]:
                    best = j
        out.append(None if best is None else events[best][2])
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        assert obs.enabled()
        run = drive()
    finally:
        jax.profiler.stop_trace()
    return run, _program_events(d)


def test_every_span_appears_with_its_metadata_and_nests(traced):
    _run, events = traced
    names = {n for _s, _e, n, _m in events}
    assert names == set(TABLE)
    for (_s, _e, name, meta), parent in zip(events, _parents(events)):
        keys, parents = TABLE[name]
        assert keys <= set(meta), (name, meta)
        assert parent in parents, (name, parent)


def test_span_counts_agree_with_the_program(traced):
    (ctx, kv, _answers, stitched), events = traced

    def of(name):
        return [m for _s, _e, n, m in events if n == name]

    assert sum(m["ops"] for m in of("repro.ctx.chunk")) == kv.stats["ops_submitted"]
    assert len(of("repro.hw.launch")) == ctx.hw.dispatch_count
    assert len(of("repro.hw.readback")) == ctx.hw.dispatch_count
    assert sum(m["delivered"] for m in of("repro.ctx.deliver")) == ctx.stats["delivered"]
    assert all(m["wait_us"] >= 0 for m in of("repro.ctx.chunk"))
    assert sum(m["pumps"] for m in of("repro.kv.read_index")) >= len(of("repro.kv.read_index"))
    drains = of("repro.snapshot.drain")
    assert len(drains) == 3 and all(m["entries"] > 0 for m in drains)
    prefixes = [m["prefix"] for m in of("repro.snapshot.seal")]
    assert prefixes == sorted(prefixes) and len(prefixes) == 3
    # each refresh copied exactly the suffix it applied — never the whole
    # stitched log — and the applied entries add up to that log
    refreshes = of("repro.kv.refresh")
    assert len(refreshes) == len(stitched)
    assert [m["copied"] for m in refreshes] == [m["applied"] for m in refreshes]
    assert any(m["copied"] for m in refreshes)
    assert sum(m["applied"] for m in refreshes) == stitched[-1]
    assert any(m["copied"] < n for m, n in zip(refreshes, stitched) if m["copied"])


def test_off_state_changes_no_result(traced):
    (ctx_on, kv_on, answers_on, _), _events = traced
    assert not obs.enabled()
    ctx, kv, answers, _ = drive()
    assert answers == answers_on
    assert ctx.full_group_log(0) == ctx_on.full_group_log(0)
    assert kv.replica(0).signature() == kv_on.replica(0).signature()
    assert ctx.hw.dispatch_count == ctx_on.hw.dispatch_count
    assert ctx.snapshots.seal(0) == ctx_on.snapshots.seal(0)


def test_grouped_pump_span_counts_its_submits(tmp_path):
    """The multi-group pump carries the pump span and the planner's round:
    its ``submits`` add up to the ops submitted, each planned cohort is one
    dispatch, and the unsharded dispatches are not spanned."""
    ctx = PaxosContext(PaxosConfig(n_acceptors=3, n_instances=64, batch=8, n_groups=4))
    svc = ConsensusService(ctx)
    submitted = 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        for step in range(6):
            for i in range(4 + step):
                svc.session(f"tenant{i}").submit(b"op%d.%d" % (step, i))
                submitted += 1
            svc.pump()
    finally:
        jax.profiler.stop_trace()
    events = _program_events(str(tmp_path))
    assert ctx.hw.dispatch_count > 0
    assert {n for _s, _e, n, _m in events} == {
        "repro.ctx.pump", "repro.ctx.retransmit", "repro.plan.round"}
    pumps = [m for _s, _e, n, m in events if n == "repro.ctx.pump"]
    assert len(pumps) == 6
    assert sum(m["submits"] for m in pumps) == submitted
    assert pumps[-1]["pending"] == len(ctx._pending)
    plans = [m for _s, _e, n, m in events if n == "repro.plan.round"]
    assert sum(m["cohorts"] for m in plans) == ctx.hw.dispatch_count
    assert all(m["members"] >= m["cohorts"] for m in plans)


def test_sharded_pump_spans_each_dispatch(tmp_path):
    """A groups-sharded context spans each dispatch: one ``repro.hw.launch``
    and one ``repro.hw.readback`` per ``dispatch_count`` step, the launches'
    ``members`` add up to the planned cohorts' members, and a context built
    with the kernels spans the precompile of its programs."""
    from repro.launch.mesh import make_group_mesh

    cfg = PaxosConfig(n_acceptors=3, n_instances=64, batch=8, n_groups=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        ctx = PaxosContext(cfg, use_kernels=True, mesh=make_group_mesh())
        svc = ConsensusService(ctx)
        for step in range(5):
            for i in range(3 + 3 * step):
                svc.session(f"tenant{i % 7}").submit(b"op%d.%d" % (step, i))
            svc.pump()
    finally:
        jax.profiler.stop_trace()
    events = _program_events(str(tmp_path))

    def of(name):
        return [m for _s, _e, n, m in events if n == name]

    [pre] = of("repro.hw.precompile")
    assert pre["programs"] == len(ctx.hw.programs) > 0
    # the folded full-width programs, whose kernel runs its rows as a loop
    assert pre["rolled"] == sum(
        p.kind == "full" and p.width > 1 for p in ctx.hw.programs) > 0
    launches, plans = of("repro.hw.launch"), of("repro.plan.round")
    assert len(launches) == len(of("repro.hw.readback")) == ctx.hw.dispatch_count > 0
    assert sum(m["cohorts"] for m in plans) == ctx.hw.dispatch_count
    assert sum(m["members"] for m in launches) == sum(m["members"] for m in plans)
    for m in launches:
        assert {"members", "lanes", "burst", "full"} <= set(m)
        assert m["lanes"] >= m["members"] and m["full"] in (0, 1)
    assert sum(len(log) for log in ctx.group_log) == sum(3 + 3 * s for s in range(5))
