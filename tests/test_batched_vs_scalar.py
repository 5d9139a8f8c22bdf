"""Cross-layer consistency: jnp batched engine vs scalar oracle vs sequential
semantics — the three implementations of acceptor/coordinator logic must
agree wherever their contracts overlap — and the served single-group context
against the scalar roles end to end."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import PaxosConfig, PaxosContext, batched
from repro.core.paxos import Acceptor, Coordinator, Learner, Msg
from repro.core.types import (
    MSG_P1A,
    MSG_P2A,
    MSG_P2B,
    MSG_SUBMIT,
    AcceptorState,
    CoordinatorState,
    MsgBatch,
)


def _batch_from(msgs, v_words=4):
    b = len(msgs)
    val = np.zeros((b, v_words), np.int32)
    for i, m in enumerate(msgs):
        val[i, 0] = m.get("val", 0)
    return MsgBatch(
        msgtype=jnp.asarray([m["t"] for m in msgs], jnp.int32),
        inst=jnp.asarray([m["i"] for m in msgs], jnp.int32),
        rnd=jnp.asarray([m["r"] for m in msgs], jnp.int32),
        vrnd=jnp.full((b,), -1, jnp.int32),
        swid=jnp.zeros((b,), jnp.int32),
        value=jnp.asarray(val),
    )


@settings(max_examples=40, deadline=None)
@given(
    msgs=st.lists(
        st.fixed_dictionaries(
            {
                "t": st.sampled_from([MSG_P2A, MSG_P1A]),
                "i": st.integers(0, 31),
                "r": st.integers(0, 4),
                "val": st.integers(-100, 100),
            }
        ),
        min_size=1,
        max_size=24,
    )
)
def test_sequential_engine_matches_scalar_oracle(msgs):
    """acceptor_sequential == the dict-based scalar Acceptor, message by message."""
    astate = AcceptorState.init(32, 4)
    oracle = Acceptor(aid=0, n_instances=32)

    batch = _batch_from(msgs)
    astate, outs = batched.acceptor_sequential(astate, batch, aid=0)

    for j, m in enumerate(msgs):
        scalar_msg = Msg(m["t"], inst=m["i"], rnd=m["r"],
                         value=int(m["val"]).to_bytes(4, "little", signed=True))
        if m["t"] == MSG_P2A:
            out = oracle.on_p2a(scalar_msg)
        else:
            out = oracle.on_p1a(scalar_msg)
        assert int(outs.msgtype[j]) == out.msgtype, (j, m)
        if out.msgtype == MSG_P2B:
            assert int(outs.vrnd[j]) == out.vrnd

    # final state agreement
    for slot, (rnd, vrnd, _value) in oracle.slots.items():
        assert int(astate.rnd[slot]) == rnd
        assert int(astate.vrnd[slot]) == vrnd


@settings(max_examples=40, deadline=None)
@given(
    n_msgs=st.integers(1, 32),
    base=st.integers(0, 100),
    rnd=st.integers(0, 3),
    seed=st.integers(0, 999),
)
def test_vectorized_matches_sequential_on_distinct_slots(n_msgs, base, rnd, seed):
    """On contiguous (distinct-slot) windows the vectorized fast path must be
    bit-identical to the sequential engine."""
    rng = np.random.default_rng(seed)
    astate0 = AcceptorState.init(256, 4)
    astate0 = AcceptorState(
        rnd=jnp.asarray(rng.integers(0, 3, 256).astype(np.int32)),
        vrnd=astate0.vrnd,
        value=astate0.value,
    )
    msgs = MsgBatch(
        msgtype=jnp.full((n_msgs,), MSG_P2A, jnp.int32),
        inst=jnp.arange(base, base + n_msgs, dtype=jnp.int32),
        rnd=jnp.full((n_msgs,), rnd, jnp.int32),
        vrnd=jnp.full((n_msgs,), -1, jnp.int32),
        swid=jnp.zeros((n_msgs,), jnp.int32),
        value=jnp.asarray(rng.integers(-9, 9, (n_msgs, 4)).astype(np.int32)),
    )
    a1, v1 = batched.acceptor_phase2(astate0, msgs, aid=1)
    a2, v2 = batched.acceptor_sequential(astate0, msgs, aid=1)
    for x, y in zip(
        (a1.rnd, a1.vrnd, a1.value, v1.msgtype, v1.rnd, v1.vrnd, v1.value),
        (a2.rnd, a2.vrnd, a2.value, v2.msgtype, v2.rnd, v2.vrnd, v2.value), strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_coordinator_contiguity_and_nops():
    cstate = CoordinatorState.init(crnd=3, next_inst=17)
    vals = jnp.zeros((8, 4), jnp.int32)
    active = jnp.asarray([1, 0, 1, 1, 0, 0, 1, 1], bool)
    cstate2, out = batched.coordinator_sequence(cstate, vals, active)
    assert int(cstate2.next_inst) == 25
    np.testing.assert_array_equal(
        np.asarray(out.inst), np.arange(17, 25, dtype=np.int32)
    )
    # NOP filler still occupies an instance (sequenced no-op, paper §3.1)
    assert (np.asarray(out.msgtype) == np.where(np.asarray(active), 3, 0)).all()


def test_learner_quorum_and_dedup():
    a, b, v = 3, 8, 4
    vt = jnp.full((a, b), MSG_P2B, jnp.int32)
    vi = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[None], (a, b))
    vr = jnp.zeros((a, b), jnp.int32)
    vv = jnp.broadcast_to(
        jnp.arange(b, dtype=jnp.int32)[None, :, None], (a, b, v)
    ).astype(jnp.int32)
    deliver, inst, win, val = batched.learner_quorum(vt, vi, vr, vv, quorum=2)
    assert np.asarray(deliver).all()

    lstate = batched.LearnerState.init(64, v)
    lstate, fresh = batched.learner_update(lstate, deliver, inst, val)
    assert np.asarray(fresh).all()
    # duplicates suppressed on replay
    lstate, fresh2 = batched.learner_update(lstate, deliver, inst, val)
    assert not np.asarray(fresh2).any()


# ---------------------------------------------------------------------------
# The served single-group path vs the scalar roles
# ---------------------------------------------------------------------------
NOP_WORD = -0x7FFFFFFF      # first value word of a burst's no-op filler lane


class _ScalarService:
    """Plain reference for a single-group ``PaxosContext``: the scalar
    ``core.paxos`` roles, driven one value at a time.

    Each pump takes the queued submissions in order, in chunks of at most
    ``batch``; a chunk of ``k`` values travels in a wire burst of the least
    power of two holding it, at least 8 and at most ``batch``, whose unused
    lanes carry the no-op filler: decided like any value, never delivered.
    A value rides its frame: the client sequence and the payload length as
    two little-endian int32 words, the payload, zero padding to the value
    width; the filler's frame is the word ``NOP_WORD`` and zeros.
    """

    def __init__(self, a: int, n: int, v: int, batch: int):
        self.n, self.v, self.batch = n, v, batch
        self.co = Coordinator(cid=0, n_instances=n)
        self.acceptors = [Acceptor(aid=i, n_instances=n) for i in range(a)]
        self.alive = [True] * a
        self.learner = Learner(lid=0, n_acceptors=a)
        self.queue: list[tuple[int, bytes]] = []
        self.delivered: list[tuple[int, bytes]] = []
        self.next_seq = 0

    def submit(self, payload: bytes) -> None:
        self.queue.append((self.next_seq, payload))
        self.next_seq += 1

    def frame(self, seq: int, payload: bytes) -> bytes:
        head = seq.to_bytes(4, "little") + len(payload).to_bytes(4, "little")
        return (head + payload).ljust(4 * self.v, b"\x00")

    def pump(self) -> None:
        queue, self.queue = self.queue, []
        for i in range(0, len(queue), self.batch):
            chunk = queue[i : i + self.batch]
            burst = 8
            while burst < len(chunk):
                burst *= 2
            burst = min(burst, self.batch)
            filler = NOP_WORD.to_bytes(4, "little", signed=True).ljust(4 * self.v, b"\x00")
            lanes = [(self.frame(seq, payload), payload) for seq, payload in chunk]
            lanes += [(filler, None)] * (burst - len(chunk))
            for frame, payload in lanes:
                p2a = self.co.on_submit(Msg(MSG_SUBMIT, value=frame))
                for aid, acc in enumerate(self.acceptors):
                    if not self.alive[aid]:
                        continue
                    vote = acc.on_p2a(p2a.clone())
                    decided = vote.msgtype == MSG_P2B and self.learner.on_p2b(vote)
                    if decided and payload is not None:
                        self.delivered.append((p2a.inst, payload))

    def kill(self, aid: int) -> None:
        self.alive[aid] = False

    def crash(self, aid: int) -> None:
        self.alive[aid] = False
        self.acceptors[aid] = Acceptor(aid=aid, n_instances=self.n)

    def restore(self, aid: int) -> int:
        """Revive ``aid`` holding, in each ring slot, the newest decided
        instance of that slot as a vote at round 0; returns how many."""
        newest: dict[int, int] = {}
        for inst in self.learner.delivered:
            slot = inst % self.n
            newest[slot] = max(inst, newest.get(slot, inst))
        acc = Acceptor(aid=aid, n_instances=self.n)
        for inst in newest.values():
            acc.slots[inst % self.n] = (0, 0, self.learner.delivered[inst])
        self.acceptors[aid] = acc
        self.alive[aid] = True
        return len(newest)

    def registers(self, aid: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        rnd = np.zeros((self.n,), np.int32)
        vrnd = np.full((self.n,), -1, np.int32)
        value = np.zeros((self.n, self.v), np.int32)
        for slot, (r, vr, val) in self.acceptors[aid].slots.items():
            rnd[slot], vrnd[slot] = r, vr
            value[slot] = np.frombuffer(val.ljust(4 * self.v, b"\x00"), "<i4")
        return rnd, vrnd, value


@pytest.mark.parametrize("fault", ["none", "kill", "crash"])
@pytest.mark.parametrize("a", [3, 5])
@pytest.mark.parametrize("burst", [1, 2, 7, 32, 128])
def test_fused_context_matches_scalar_roles(burst, a, fault):
    """A single-group fused ``PaxosContext`` (the path a one-group KV
    deployment serves through) decides the same ``(inst, value)`` log, in
    the same delivery order, and leaves the same acceptor registers as the
    scalar roles fed the same submissions: ``burst`` values per pump over
    12 pumps, the 512-slot ring lapping for the larger bursts, with one
    acceptor killed at pump 4, or crashed with state loss at pump 4 and
    restored at pump 8."""
    n, v, batch, pumps = 512, 16, 32, 12
    ctx = PaxosContext(
        PaxosConfig(n_acceptors=a, n_instances=n, value_words=v, batch=batch),
        use_kernels=False, fused=True,
    )
    ref = _ScalarService(a, n, v, batch)
    victim = a // 2
    for p in range(pumps):
        if p == 4 and fault == "kill":
            ctx.hw.kill_acceptor(victim)
            ref.kill(victim)
        if p == 4 and fault == "crash":
            ctx.crash_acceptor(victim)
            ref.crash(victim)
        if p == 8 and fault == "crash":
            assert ctx.restore_acceptor(victim) == ref.restore(victim)
        for j in range(burst):
            payload = f"p{p}v{j}".encode()
            ctx.submit(payload)
            ref.submit(payload)
        ctx.pump()
        ref.pump()
        assert ctx.quiescent(), (p, ctx.stats)
        assert ctx.delivered_log == ref.delivered, p
    assert len(ref.delivered) == pumps * burst
    assert ctx.stats["retransmits"] == 0
    stack = ctx.hw.stack
    for aid in range(a):
        want = ref.registers(aid)
        got = (
            np.asarray(stack.rnd)[aid],
            np.asarray(stack.vrnd)[aid],
            np.asarray(stack.value)[aid],
        )
        for w, g in zip(want, got, strict=True):
            np.testing.assert_array_equal(g, w, err_msg=f"acceptor {aid}")
