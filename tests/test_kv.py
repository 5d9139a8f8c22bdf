"""Fast-lane unit tests for the replicated KV tier (serve/kv.py).

Covers the op codec (round-trip + malformed-frame rejection), the
deterministic apply loop's cas/tombstone semantics, lease validity and
read-watermark monotonicity, the consensus-free read path's dispatch-count
pin, the payload-width door guards, the typed Session surface, and
snapshot state transfer applying (never replaying) through the dataplane,
and the apply cursor's suffix read: a refresh copies the entries it
applies, never the history, and lands on the state a fresh replay of the
whole stitched log gives.
"""
import contextlib
import random
import sys
import types

import pytest

sys.path.insert(0, "src")

from repro.core.api import PaxosContext  # noqa: E402
from repro.serve import kv as kv_module  # noqa: E402
from repro.core.snapshot import RingOverflowError  # noqa: E402
from repro.core.types import PaxosConfig  # noqa: E402
from repro.serve.engine import ConsensusService, Session, Ticket  # noqa: E402
from repro.serve.kv import (  # noqa: E402
    OP_CAS,
    OP_DELETE,
    OP_GET,
    OP_PUT,
    GroupReplica,
    KvCodecError,
    KvOp,
    ReplicatedKV,
    decode_op,
    encode_op,
)

A = 3
CFG = PaxosConfig(n_acceptors=A, n_instances=64, batch=8, n_groups=2)


def _service(cfg=CFG):
    return ConsensusService(PaxosContext(cfg))


# ---------------------------------------------------------------------------
# Op codec
# ---------------------------------------------------------------------------
def test_codec_round_trips_every_op_shape():
    ops = [
        KvOp(OP_PUT, b"key", b"value", None, 0xDEADBEEF, 7),
        KvOp(OP_PUT, b"", b"", None, 0, 0),               # empty key/value
        KvOp(OP_DELETE, b"gone", b"", None, 1, 2),
        KvOp(OP_CAS, b"k", b"new", b"old", 42, 3),        # expect a value
        KvOp(OP_CAS, b"k", b"new", b"", 42, 4),           # expect empty value
        KvOp(OP_CAS, b"k", b"new", None, 42, 5),          # expect ABSENT
        KvOp(OP_GET, b"", b"", None, 99, 6),              # read-index marker
    ]
    for op in ops:
        assert decode_op(encode_op(op)) == op, op
    # expect=None and expect=b"" are distinct frames (absent vs empty)
    assert encode_op(ops[4]) != encode_op(ops[5])


def test_codec_rejects_malformed_frames():
    good = encode_op(KvOp(OP_CAS, b"key", b"val", b"old", 5, 9))
    with pytest.raises(KvCodecError, match="truncated"):
        decode_op(good[:10])
    with pytest.raises(KvCodecError, match="magic"):
        decode_op(b"\x00" + good[1:])
    with pytest.raises(KvCodecError, match="version"):
        decode_op(good[:1] + b"\x7f" + good[2:])
    with pytest.raises(KvCodecError, match="opcode"):
        decode_op(good[:2] + b"\x7f" + good[3:])
    with pytest.raises(KvCodecError, match="flags"):
        decode_op(good[:3] + b"\x80" + good[4:])
    with pytest.raises(KvCodecError, match="length"):
        decode_op(good + b"extra")                         # trailing bytes
    with pytest.raises(KvCodecError, match="length"):
        decode_op(good[:-1])                               # short body
    # expect flag only makes sense on cas
    put = bytearray(encode_op(KvOp(OP_PUT, b"k", b"v")))
    put[3] |= 1                                            # forge expect flag
    with pytest.raises(KvCodecError, match="non-cas"):
        decode_op(bytes(put))
    # expect bytes without the flag
    cas = bytearray(good)
    cas[3] = 0
    with pytest.raises(KvCodecError, match="without the expect flag"):
        decode_op(bytes(cas))
    # unencodable ops are refused at the encoder door
    with pytest.raises(KvCodecError, match="unknown opcode"):
        encode_op(KvOp(99, b"k"))
    with pytest.raises(KvCodecError, match="only meaningful on cas"):
        encode_op(KvOp(OP_PUT, b"k", b"v", expect=b"x"))


# ---------------------------------------------------------------------------
# Apply loop: cas semantics, tombstones, versions, RYW counters
# ---------------------------------------------------------------------------
def _log(*ops):
    return [(i, encode_op(op)) for i, op in enumerate(ops)]


def test_replica_cas_and_tombstone_semantics():
    rep = GroupReplica()
    rep.apply_log(_log(
        KvOp(OP_CAS, b"k", b"v0", None, 1, 1),     # create iff absent: applies
        KvOp(OP_CAS, b"k", b"xx", None, 1, 2),     # expect-absent now fails
        KvOp(OP_CAS, b"k", b"v1", b"v0", 1, 3),    # matches: applies
        KvOp(OP_CAS, b"k", b"xx", b"v0", 1, 4),    # stale expect: no-op
        KvOp(OP_PUT, b"d", b"x", None, 2, 1),
        KvOp(OP_DELETE, b"d", b"", None, 2, 2),    # tombstone, not removal
        KvOp(OP_GET, b"", b"", None, 3, 1),        # marker: no state change
    ))
    assert rep.state[b"k"] == (b"v1", 2)           # two applied mutations
    assert rep.state[b"d"] == (None, 2)            # tombstone bumps version
    # every op advances its session's RYW counter, applied or not
    assert rep.applied_counter == {1: 4, 2: 2, 3: 1}
    # cas against a tombstone is expect-absent semantics
    rep.apply_log(_log(
        KvOp(OP_CAS, b"k", b"v0", None, 1, 1),
        KvOp(OP_CAS, b"k", b"xx", None, 1, 2),
        KvOp(OP_CAS, b"k", b"v1", b"v0", 1, 3),
        KvOp(OP_CAS, b"k", b"xx", b"v0", 1, 4),
        KvOp(OP_PUT, b"d", b"x", None, 2, 1),
        KvOp(OP_DELETE, b"d", b"", None, 2, 2),
        KvOp(OP_GET, b"", b"", None, 3, 1),
        KvOp(OP_CAS, b"d", b"back", None, 4, 1),   # revives the deleted key
    ))
    assert rep.state[b"d"] == (b"back", 3)
    assert rep.applied_len == 8
    # the cursor refuses a shrinking view of its segment
    with pytest.raises(ValueError, match="shrank"):
        rep.apply_log([])


def test_read_watermark_is_monotone_and_tracks_the_log():
    svc = _service()
    kv = ReplicatedKV(svc)
    s = kv.session("mono")
    gid = svc.group_of("mono")
    seen = [kv.read_watermark(gid)]
    for wave in range(3):
        for k in range(4):
            s.put(f"w{wave}k{k}".encode(), b"v")
        svc.run_until_quiescent()
        kv.refresh()
        seen.append(kv.read_watermark(gid))
        assert seen[-1] == len(svc.ctx.full_group_log(gid))
    assert seen == sorted(seen) and seen[-1] == 12
    # refresh is idempotent: no new entries, no watermark motion
    kv.refresh()
    assert kv.read_watermark(gid) == 12


# ---------------------------------------------------------------------------
# Consensus-free reads: lease validity and the dispatch-count pin
# ---------------------------------------------------------------------------
def test_leased_get_dispatches_nothing():
    svc = _service()
    kv = ReplicatedKV(svc)
    s = kv.session("alice")
    s.put(b"k", b"v1")
    svc.run_until_quiescent()
    base = svc.ctx.hw.dispatch_count
    assert s.lease_valid is False        # pending until refresh prunes it
    for _ in range(5):
        assert s.get(b"k") == b"v1"
        assert s.lease_valid
    assert s.get(b"missing") is None
    assert svc.ctx.hw.dispatch_count == base    # zero wire-path launches
    assert kv.stats == {"leased_gets": 6, "read_index_gets": 0,
                        "ops_submitted": 1}


def test_pending_write_forces_read_index():
    svc = _service()
    kv = ReplicatedKV(svc)
    s = kv.session("alice")
    s.put(b"k", b"v1")
    svc.run_until_quiescent()
    assert s.get(b"k") == b"v1"          # leased
    s.put(b"k", b"v2")                   # in flight: lease breaks
    base = svc.ctx.hw.dispatch_count
    assert s.get(b"k") == b"v2"          # read-index waits out the write
    assert svc.ctx.hw.dispatch_count > base
    assert kv.stats["read_index_gets"] == 1
    # the read-index round re-validated the lease
    assert s.lease_valid
    assert s.get(b"k") == b"v2"
    assert kv.stats["leased_gets"] == 2


def test_lease_survives_unrelated_retire_but_not_own():
    cfg = PaxosConfig(n_acceptors=A, n_instances=64, batch=8, n_groups=4)
    svc = _service(cfg)
    kv = ReplicatedKV(svc)
    sid = "alice"
    mine = svc.group_of(sid)
    other = next(g for g in range(4) if g != mine)
    s = kv.session(sid)
    s.put(b"k", b"v1")
    svc.run_until_quiescent()
    assert s.get(b"k") == b"v1"

    # membership event that does NOT move this session: epoch bumps, but the
    # segment is unchanged — the lease re-validates host-side, no dispatch
    svc.retire_group(other)
    base = svc.ctx.hw.dispatch_count
    assert s.get(b"k") == b"v1"
    assert svc.ctx.hw.dispatch_count == base
    assert kv.stats["read_index_gets"] == 0

    # retiring the session's OWN group moves it: stale lease, read-index
    # fallback, and the value survives via the stitched archive
    svc.retire_group(mine)
    assert s.get(b"k") == b"v1"
    assert svc.ctx.hw.dispatch_count > base
    assert kv.stats["read_index_gets"] == 1
    assert s.lease_valid                 # re-validated at the new epoch


# ---------------------------------------------------------------------------
# Payload-width door guards
# ---------------------------------------------------------------------------
def test_oversized_payload_rejected_at_every_door():
    svc = _service()
    limit = CFG.max_payload_bytes
    assert limit == CFG.value_words * 4 - 8
    fat = b"x" * (limit + 1)
    with pytest.raises(ValueError, match=f"at most {limit} payload"):
        svc.ctx.submit(fat, group=0)
    with pytest.raises(ValueError, match=f"at most {limit} payload"):
        svc.session("s").submit(fat)
    with pytest.raises(ValueError, match=f"at most {limit} payload"):
        with pytest.warns(DeprecationWarning):
            svc.submit("s", fat)
    # the limit itself still fits
    svc.session("s").submit(b"x" * limit)
    svc.run_until_quiescent()
    assert svc.session("s").read() == [b"x" * limit]


# ---------------------------------------------------------------------------
# Typed Session surface + deprecation shims
# ---------------------------------------------------------------------------
def test_session_handle_and_ticket():
    svc = _service()
    sess = svc.session("u1")
    assert isinstance(sess, Session)
    assert sess.group == svc.group_of("u1")
    t = sess.submit(b"op0")
    assert isinstance(t, Ticket)
    assert t.group == sess.group
    gid, seq = t                          # historical tuple unpacking
    assert (gid, seq) == (t.group, t.seq)
    svc.run_until_quiescent()
    assert sess.read() == [b"op0"]
    assert [p for _i, p in sess.delivered()] == [b"op0"]
    # the old loose surface still works, loudly
    with pytest.warns(DeprecationWarning, match="session_id"):
        t2 = svc.submit("u1", b"op1")
    assert isinstance(t2, Ticket) and t2.group == t.group
    svc.run_until_quiescent()
    with pytest.warns(DeprecationWarning):
        assert [p for _i, p in svc.delivered("u1")] == [b"op0", b"op1"]


def test_ring_overflow_context_dict():
    cfg = PaxosConfig(n_acceptors=A, n_instances=16, batch=8)
    ctx = PaxosContext(cfg, fused=True, snapshots=True)
    for i in range(16):
        ctx.submit(f"m{i}".encode())
    ctx.run_until_quiescent()
    with pytest.raises(RingOverflowError) as ei:
        ctx.submit(b"overflow")
        ctx.pump()
    e = ei.value
    assert e.context == {
        "group": e.group,
        "base": e.base,
        "burst": e.burst,
        "boundary": e.boundary,
        "attempted": e.attempted,
    }
    assert e.context["attempted"] > e.context["boundary"]


# ---------------------------------------------------------------------------
# Snapshot state transfer: applied host-side, never replayed
# ---------------------------------------------------------------------------
def test_adopted_snapshot_is_applied_not_replayed():
    cfg = PaxosConfig(n_acceptors=A, n_instances=16, batch=8, n_groups=2)
    ctx1 = PaxosContext(cfg, snapshots=True)
    svc1 = ConsensusService(ctx1)
    kv1 = ReplicatedKV(svc1)
    sid = next(f"s{i}" for i in range(64) if svc1.group_of(f"s{i}") == 1)
    s = kv1.session(sid)
    for k in range(8):
        s.put(f"k{k}".encode(), f"v{k}".encode())
    svc1.run_until_quiescent()
    ctx1.snapshot_group(1)               # compact below the watermark
    for k in range(8):
        s.put(f"k{k}".encode(), f"w{k}".encode())
    svc1.run_until_quiescent()
    kv1.refresh()
    sig = kv1.replica(1).signature()
    assert sig[1] == 16

    snap = ctx1.snapshot_group(1)
    prefix = list(ctx1.snapshots.log_prefix(1))

    ctx2 = PaxosContext(cfg, snapshots=True)
    svc2 = ConsensusService(ctx2)
    kv2 = ReplicatedKV(svc2)
    svc2.retire_group(1)                 # free the slot for the transfer
    gid = svc2.adopt_group(snap, log_prefix=prefix)
    assert gid == 1
    kv2.refresh()
    # bit-identical replica state, reconstructed from the sealed prefix
    assert kv2.replica(1).signature() == sig
    # ...without a single wire-path launch: applied, not replayed
    assert ctx2.hw.dispatch_count == 0


# ---------------------------------------------------------------------------
# The apply cursor reads the stitched log's suffix, never the whole history
# ---------------------------------------------------------------------------
class _SpanLog:
    """Stands in for ``repro.obs`` inside ``serve/kv.py``: spans are on and
    each span's metadata is kept, so a test reads ``copied``/``applied``
    without a profiler session."""

    def __init__(self):
        self.spans: list[dict] = []

    def enabled(self) -> bool:
        return True

    @contextlib.contextmanager
    def span(self, name, **meta):
        rec = dict(meta, name=name)
        self.spans.append(rec)
        yield types.SimpleNamespace(set_metadata=rec.update)

    def refreshes(self) -> list[dict]:
        return [m for m in self.spans if m["name"] == "repro.kv.refresh"]


def test_refresh_copies_only_the_new_suffix(monkeypatch):
    """A refresh after k new deliveries copies k entries, whether the
    history behind the cursor is about 1,000 entries or 16 times that,
    compacted into the snapshot prefix many times over."""
    spans = _SpanLog()
    monkeypatch.setattr(kv_module, "obs", spans)
    cfg = PaxosConfig(n_acceptors=A, n_instances=4096, batch=128)
    copied = []
    for history in (1_000, 16_000):
        ctx = PaxosContext(cfg, fused=True, snapshots=True)
        svc = ConsensusService(ctx)
        kv = ReplicatedKV(svc)
        s = kv.session("hist")
        compactions = 0
        while len(ctx.full_group_log(0)) < history:
            for i in range(history // 4):
                s.put(b"k%d" % (i % 97), b"v%d" % i)
            svc.run_until_quiescent()
            kv.refresh()
            ctx.snapshot_group(0)
            compactions += 1
        assert compactions >= 2
        assert len(ctx.snapshots.log_prefix(0)) >= history
        for i in range(40):                       # live entries past the cut
            s.put(b"live%d" % i, b"v")
        svc.run_until_quiescent()
        kv.refresh()
        stitched = len(ctx.full_group_log(0))
        for i in range(5):                        # k = 5 new deliveries
            s.put(b"new%d" % i, b"v")
        svc.run_until_quiescent()
        k = len(ctx.full_group_log(0)) - stitched
        kv.refresh()
        last = spans.refreshes()[-1]
        assert last["applied"] == k == 5
        copied.append(last["copied"])
        assert kv.read_watermark(0) == stitched + k
    assert copied == [5, 5]


def _replayed(ctx, gid):
    """A fresh replica that applied the whole stitched log at once."""
    rep = GroupReplica()
    rep.apply_log(ctx.full_group_log(gid))
    return rep.signature()


def _check_replicas(ctx, kvs):
    """Each of ``kvs`` (just refreshed) against a replay, and the suffix
    read against a slice of the whole stitched log."""
    for gid in ctx.live_groups():
        full = ctx.full_group_log(gid)
        for start in range(len(full) + 2):
            assert ctx.group_log_since(gid, start) == full[start:], (gid, start)
        for kv in kvs:
            assert kv.replica(gid).signature() == _replayed(ctx, gid), gid


def _random_ops(rng, svc, kvs, sessions, steps, snapshots):
    """Puts, deletes, cas, gets (leased or read-index), pumps, refreshes and
    snapshots at seeded random points; every refresh is checked against a
    replay.  Returns the compactions made."""
    ctx = svc.ctx
    keys = [b"a", b"b", b"c", b"d"]
    compactions = 0
    for _ in range(steps):
        s = rng.choice(sessions)
        key = rng.choice(keys)
        r = rng.random()
        if r < 0.25:
            s.put(key, b"p%d" % rng.randrange(1000))
        elif r < 0.35:
            s.delete(key)
        elif r < 0.45:
            s.cas(key, rng.choice([None, b"", b"p1", s.kv.lookup(s.id, key)]),
                  b"c%d" % rng.randrange(1000))
        elif r < 0.6:
            s.get(key)                   # refreshes the session's own tier
            _check_replicas(ctx, [s.kv])
        elif r < 0.75:
            svc.pump()
        elif r < 0.88:
            kv = rng.choice(kvs)
            kv.refresh()
            _check_replicas(ctx, [kv])
        elif snapshots:
            svc.run_until_quiescent()
            ctx.snapshot_group(rng.choice(ctx.live_groups()))
            compactions += 1
    svc.run_until_quiescent()
    for kv in kvs:
        kv.refresh()
    _check_replicas(ctx, kvs)
    return compactions


@pytest.mark.parametrize(
    "case", ["attached_after_compaction", "adopted_snapshot", "no_snapshots"]
)
def test_suffix_apply_matches_a_whole_log_replay(case):
    """After every refresh each replica equals a fresh replay of
    ``full_group_log``, and ``group_log_since(gid, s)`` equals
    ``full_group_log(gid)[s:]`` for every ``s``: through compactions that
    move entries the cursor has not reached into the prefix, for a replica
    first attached after a compaction, over an adopted snapshot, and
    without snapshots."""
    rng = random.Random(f"suffix-{case}")
    cfg = PaxosConfig(n_acceptors=A, n_instances=256, batch=8, n_groups=2)
    ctx = PaxosContext(cfg, snapshots=case != "no_snapshots")
    svc = ConsensusService(ctx)
    reads = []                           # (start, prefix length) per read

    def record_reads(ctx):
        since = ctx.group_log_since

        def recorded(gid=0, start=0):
            prefix = ctx.snapshots.log_prefix(gid) if ctx.snapshots else []
            reads.append((start, len(prefix)))
            return since(gid, start)

        ctx.group_log_since = recorded

    record_reads(ctx)
    kv = ReplicatedKV(svc)
    sessions = [kv.session(f"s{i}") for i in range(6)]
    assert {svc.group_of(s.id) for s in sessions} == {0, 1}
    made = _random_ops(rng, svc, [kv], sessions, 120, case != "no_snapshots")
    kvs = [kv]
    if case == "attached_after_compaction":
        assert made >= 2 and ctx.snapshots.log_prefix(0)
        late = ReplicatedKV(svc)
        kvs.append(late)
        late.refresh()                   # its first read starts in the prefix
        assert reads[-1][0] < reads[-1][1] or reads[-2][0] < reads[-2][1]
        _check_replicas(ctx, kvs)
        sessions += [late.session(f"late{i}") for i in range(3)]
        made += _random_ops(rng, svc, kvs, sessions, 120, True)
    elif case == "adopted_snapshot":
        assert made >= 2
        snap = ctx.snapshot_group(1)
        prefix = list(ctx.snapshots.log_prefix(1))
        ctx2 = PaxosContext(cfg, snapshots=True)
        svc2 = ConsensusService(ctx2)
        kv2 = ReplicatedKV(svc2)
        svc2.retire_group(1)
        assert svc2.adopt_group(snap, log_prefix=prefix) == 1
        kv2.refresh()
        assert kv2.replica(1).signature() == _replayed(ctx, 1)
        assert ctx2.hw.dispatch_count == 0
        record_reads(ctx2)
        sessions2 = [kv2.session(f"t{i}") for i in range(6)]
        made += _random_ops(rng, svc2, [kv2], sessions2, 120, True)
        ctx = ctx2
    # some refresh read from inside the prefix, past a cursor that a
    # compaction overtook (0 < start < prefix length)
    if case != "no_snapshots":
        assert any(0 < start < p for start, p in reads), reads
    assert kv.stats["read_index_gets"] and kv.stats["leased_gets"]
