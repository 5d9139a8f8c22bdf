"""Linearizability chaos suite for the replicated KV tier (DESIGN.md §10).

Two properties, each on all four backends (jnp + pallas kernels, unsharded +
groups-sharded):

* **Twin apply-state equality** — raw encoded KV ops (put / delete / cas,
  cas with both hit and deliberate miss expects) driven through the
  multi-group service under chaos (coordinator failover, acceptor crash
  WITH state loss + snapshot-restore, snapshot compaction, retire / create
  membership churn) produce replica state **bit-equal** to a fresh apply
  loop over independent single-group twins fed the identical schedule at
  identical pump cadence — at every retirement instant and at the end.

* **Zero stale reads** — KVSession clients under membership churn never
  observe a stale value: every ``get`` equals the session's last issued
  write (single-writer keys) AND an independent oracle that linearly
  decodes the session's stitched segment chain.  Every *leased* get is
  pinned consensus-free by the dataplane's dispatch counter; the schedule
  must exercise both the leased path and the read-index fallback.

* **A dict state machine** — ``KVSession`` clients over a one-group KV
  deployment (the shape of the benchmark's ``kv_a3``) under YCSB-shaped
  mixes: every ``get`` and the final state equal a plain ``dict`` applying
  the acknowledged ops in log order.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import PaxosConfig, PaxosContext
from repro.launch.mesh import make_group_mesh
from repro.serve.engine import ConsensusService
from repro.serve.kv import (
    OP_CAS,
    OP_DELETE,
    OP_PUT,
    GroupReplica,
    KvOp,
    ReplicatedKV,
    decode_op,
    encode_op,
)

# the chaos suites are marked slow: skipped in the fast CI lane
slow = pytest.mark.slow

A = 3
KEYS = [f"key{i}".encode() for i in range(4)]


def _cfg(g: int) -> PaxosConfig:
    return PaxosConfig(n_acceptors=A, n_instances=256, batch=8, n_groups=g)


CFG1 = PaxosConfig(n_acceptors=A, n_instances=256, batch=8)


def _oracle_sig(log):
    """One-shot fresh apply loop over a full twin log — the unbounded
    oracle the service-maintained incremental replica must bit-match."""
    rep = GroupReplica()
    rep.apply_log(list(log))
    return rep.signature()


# ---------------------------------------------------------------------------
# Part A: twin apply-state equality under chaos
# ---------------------------------------------------------------------------
def run_kv_twins(
    seed: int, g: int, use_kernels: bool, sharded: bool, waves: int = 12
) -> None:
    mesh = make_group_mesh() if sharded else None
    ctx = PaxosContext(_cfg(g), use_kernels=use_kernels, mesh=mesh,
                       snapshots=True)
    svc = ConsensusService(ctx)
    kv = ReplicatedKV(svc)
    twins = [
        PaxosContext(CFG1, use_kernels=use_kernels, fused=True,
                     snapshots=True)
        for _ in range(g)
    ]
    rng = np.random.default_rng(seed)
    counters = [0] * g                # synthetic per-group session counters

    def submit(gid: int) -> None:
        counters[gid] += 1
        c = counters[gid]
        key = KEYS[int(rng.integers(len(KEYS)))]
        r = rng.random()
        if r < 0.5:
            op = KvOp(OP_PUT, key, f"g{gid}c{c}".encode(), None,
                      1000 + gid, c)
        elif r < 0.7:
            op = KvOp(OP_DELETE, key, b"", None, 1000 + gid, c)
        else:
            # mix of expect-absent and (mostly-missing) value expects: both
            # the applied and the committed-no-op cas paths must replicate
            expect = None if r < 0.8 else f"g{gid}c{int(rng.integers(c))}".encode()
            op = KvOp(OP_CAS, key, f"cas{c}".encode(), expect, 1000 + gid, c)
        p = encode_op(op)
        ctx.submit(p, group=gid)
        twins[gid].submit(p)

    def pump() -> None:
        ctx.pump()
        for t in twins:
            if t is not None:
                t.pump()

    churn_gid = g - 1
    for w in range(waves):
        if w == 3:                    # coordinator failover in group 0
            ctx.fail_coordinator(group=0)
            twins[0].fail_coordinator()
        if w == 5:
            ctx.restore_hardware_coordinator(group=0)
            twins[0].restore_hardware_coordinator()
        if w == 6:                    # crash WITH state loss in group 0
            ctx.crash_acceptor(2, group=0)
            twins[0].crash_acceptor(2)
        if w == 9:
            # snapshot-advanced watermark: the rebuild is prefix + suffix
            assert ctx.snapshots.watermark(0) > 0
            assert ctx.restore_acceptor(2, group=0) == twins[
                0
            ].restore_acceptor(2), seed
        if w == 7:                    # membership churn, mid-traffic
            gen = svc.group_generation(churn_gid)
            svc.retire_group(churn_gid)
            kv.refresh()              # finalizes the archived segment
            assert kv.replica(churn_gid, gen).signature() == _oracle_sig(
                twins[churn_gid].delivered_log
            ), (seed, churn_gid)
            twins[churn_gid] = None
            counters[churn_gid] = 0
        if w == 10:
            assert svc.create_group() == churn_gid
            twins[churn_gid] = PaxosContext(
                CFG1, use_kernels=use_kernels, fused=True, snapshots=True
            )
        for gid in ctx.live_groups():
            for _ in range(int(rng.integers(1, 5))):
                submit(gid)
        pump()
        if (w + 1) % 4 == 0:          # compaction mid-stream, both sides
            for gid in ctx.live_groups():
                snap = ctx.snapshot_group(gid)
                tsnap = twins[gid].snapshot_group()
                assert snap.watermark == tsnap.watermark, (seed, gid)
                assert snap.seal == tsnap.seal, (seed, gid)
        kv.refresh()                  # incremental host-side apply
    for _ in range(30):               # heal: outlast retransmit cycles
        pump()
    kv.refresh()
    for gid in ctx.live_groups():
        # the log itself is bit-equal (the established chaos contract)...
        assert ctx.full_group_log(gid) == twins[gid].full_group_log(), (
            seed, gid,
        )
        # ...and so is the *applied state*: the incrementally-maintained
        # replica matches a one-shot oracle over the twin's unbounded log
        assert kv.replica(gid).signature() == _oracle_sig(
            twins[gid].full_group_log()
        ), (seed, gid)
    assert not ctx._pending


@slow
@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("seed", [0, 1])
def test_kv_twins_unsharded(seed, use_kernels):
    run_kv_twins(seed, g=3, use_kernels=use_kernels, sharded=False)


@slow
@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("seed", [2, 3])
def test_kv_twins_sharded(seed, use_kernels):
    run_kv_twins(seed, g=2, use_kernels=use_kernels, sharded=True)


# ---------------------------------------------------------------------------
# Part B: zero stale reads through KVSession under churn
# ---------------------------------------------------------------------------
def _oracle_get(svc, sid, key):
    """Independent read oracle: linearly decode the session's stitched
    segment chain.  For single-writer keys this is exactly the last issued
    write that survived (a write pending at its group's retirement died on
    the wire — the schedule quiesces before every retire so none do)."""
    val = None
    for seg in svc.session_chain(sid):
        for _inst, payload in svc.log_segment(*seg):
            op = decode_op(payload)
            if op.key != key:
                continue
            if op.op == OP_PUT:
                val = op.value
            elif op.op == OP_DELETE:
                val = None
    return val


def run_kv_sessions(
    seed: int, g: int, use_kernels: bool, sharded: bool, waves: int = 6
) -> None:
    mesh = make_group_mesh() if sharded else None
    ctx = PaxosContext(_cfg(g), use_kernels=use_kernels, mesh=mesh,
                       snapshots=True)
    svc = ConsensusService(ctx)
    kv = ReplicatedKV(svc)
    rng = np.random.default_rng(seed)
    sids = [f"user-{i}" for i in range(2 * g)]
    last: dict = {}                   # sid -> last issued value for its key
    for w in range(waves):
        for sid in sids:
            s = kv.session(sid)
            key = f"k-{sid}".encode()  # single-writer: exact staleness oracle
            if rng.random() < 0.8:
                v = f"{sid}w{w}".encode()
                s.put(key, v)
                last[sid] = v
            else:
                s.delete(key)
                last[sid] = None
        svc.run_until_quiescent()
        for sid in sids:
            s = kv.session(sid)
            before = dict(kv.stats)
            base = ctx.hw.dispatch_count
            v = s.get(f"k-{sid}".encode())
            assert v == last[sid], (seed, w, sid)           # never stale
            assert v == _oracle_get(svc, sid, f"k-{sid}".encode()), (
                seed, w, sid,
            )
            if kv.stats["leased_gets"] > before["leased_gets"]:
                # the consensus-free claim, pinned: a leased get launched
                # NOTHING on the dataplane
                assert ctx.hw.dispatch_count == base, (seed, w, sid)
        # membership churn between waves (quiescent: no write dies)
        if w == 1:
            svc.retire_group(svc.group_of(sids[0]))
        if w == 3 and len(ctx.live_groups()) < g:
            svc.create_group()
    # the schedule exercised BOTH read paths
    assert kv.stats["leased_gets"] > 0
    assert kv.stats["read_index_gets"] > 0


@slow
@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("seed", [0, 1])
def test_kv_sessions_unsharded(seed, use_kernels):
    run_kv_sessions(seed, g=3, use_kernels=use_kernels, sharded=False)


@slow
@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("seed", [2, 3])
def test_kv_sessions_sharded(seed, use_kernels):
    run_kv_sessions(seed, g=2, use_kernels=use_kernels, sharded=True)


# ---------------------------------------------------------------------------
# Part C: the KV tier against a dict state machine
# ---------------------------------------------------------------------------
# (get, put, cas, delete) shares of each traffic mix
MIXES = {
    "ycsb_a": (0.5, 0.5, 0.0, 0.0),
    "ycsb_b": (0.95, 0.05, 0.0, 0.0),
    "cas_delete": (0.1, 0.3, 0.35, 0.25),
    "put_only": (0.0, 1.0, 0.0, 0.0),
}


def _dict_apply(state: dict, op: tuple) -> None:
    """The plain reference: one acknowledged op on a ``dict``."""
    kind, key, value, expect = op
    if kind == "put":
        state[key] = value
    elif kind == "delete":
        state.pop(key, None)
    elif state.get(key) == expect:      # cas; expect None = key absent
        state[key] = value


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_kv_matches_dict_state_machine(mix, seed):
    """Eight sessions drive 240 ops of ``mix`` over 64 Zipf(0.99) keys
    through ``KVSession`` on a one-group fused context with snapshots,
    pumping at random points and compacting once mid-run.  One group
    decides in submission order, and a pump delivers everything submitted
    before it, so the acknowledged prefix of the issued writes is known at
    every get: each get must read the dict over that prefix, the final
    replica state the dict over every write, and the log must hold each
    write and each read-index marker once."""
    ctx = PaxosContext(
        PaxosConfig(n_acceptors=A, n_instances=4096, value_words=16, batch=128),
        use_kernels=False, fused=True, snapshots=True,
    )
    svc = ConsensusService(ctx)
    kv = ReplicatedKV(svc)
    rng = np.random.default_rng(seed)
    keys = [f"k{i}".encode() for i in range(64)]
    zipf = 1.0 / np.arange(1, 65) ** 0.99
    zipf = zipf[rng.permutation(64)] / zipf.sum()
    sessions = [kv.session(f"c{i}") for i in range(8)]
    issued: list[tuple] = []          # every write, in submission order
    latest: dict = {}                 # the dict over every issued write
    acked: dict = {}                  # the dict over the acknowledged ones
    n_acked = n_applied = 0

    def catch_up() -> None:
        nonlocal n_applied
        for op in issued[n_applied:n_acked]:
            _dict_apply(acked, op)
        n_applied = n_acked

    for step in range(240):
        s = sessions[int(rng.integers(len(sessions)))]
        key = keys[int(rng.choice(64, p=zipf))]
        kind = rng.choice(["get", "put", "cas", "delete"], p=MIXES[mix])
        value = f"{s.id}.{step}".encode()
        if kind == "get":
            before = kv.stats["read_index_gets"]
            got = s.get(key)
            if kv.stats["read_index_gets"] > before:
                n_acked = len(issued)   # the read-index op pumped them all
            catch_up()
            assert got == acked.get(key), (step, key)
            continue
        if kind == "put":
            s.put(key, value)
            issued.append(("put", key, value, None))
        elif kind == "delete":
            s.delete(key)
            issued.append(("delete", key, b"", None))
        else:
            r = rng.random()
            expect = latest.get(key) if r < 0.5 else None if r < 0.75 else b"stale"
            s.cas(key, expect, value)
            issued.append(("cas", key, value, expect))
        _dict_apply(latest, issued[-1])
        if rng.random() < 0.25:
            svc.pump()
            assert ctx.quiescent()
            n_acked = len(issued)
        if step == 120:
            ctx.snapshot_group(0)
    svc.run_until_quiescent()
    assert ctx.quiescent()
    kv.refresh()
    n_acked = len(issued)
    catch_up()
    state = {k: v for k, (v, _ver) in kv.replica(0).state.items() if v is not None}
    assert state == acked
    assert len(ctx.full_group_log(0)) == len(issued) + kv.stats["read_index_gets"]
    if MIXES[mix][0]:
        assert kv.stats["leased_gets"] and kv.stats["read_index_gets"]
