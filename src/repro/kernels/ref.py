"""Pure-jnp oracles for every Pallas kernel in this package.

Each function mirrors a kernel's *exact* contract (same inputs/outputs); the
kernel test suite sweeps shapes and dtypes asserting allclose/array_equal
against these.  Implementations delegate to ``repro.core.batched`` — the jnp
dataplane engine — so the oracle and the system share one source of protocol
truth.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core import batched
from repro.core.types import (
    MSG_NOP,
    MSG_P2A,
    MSG_P2B,
    AcceptorState,
    MsgBatch,
)

NO_ROUND = -1


def acceptor_phase2_window(
    st_rnd, st_vrnd, st_val, base, aid, msgtype, msg_rnd, msg_val
) -> tuple[jax.Array, ...]:
    """Oracle for kernels.acceptor.acceptor_phase2_window."""
    n = st_rnd.shape[0]
    b = msgtype.shape[0]
    inst = (jnp.asarray(base, jnp.int32) + jnp.arange(b, dtype=jnp.int32)) % n
    msgs = MsgBatch(
        msgtype=msgtype,
        inst=inst,
        rnd=msg_rnd,
        vrnd=jnp.full((b,), NO_ROUND, jnp.int32),
        swid=jnp.zeros((b,), jnp.int32),
        value=msg_val,
    )
    astate = AcceptorState(st_rnd, st_vrnd, st_val)
    astate, votes = batched.acceptor_phase2(astate, msgs, aid=aid)
    return (
        astate.rnd,
        astate.vrnd,
        astate.value,
        votes.msgtype,
        votes.rnd,
        votes.vrnd,
        votes.swid,
        votes.value,
    )


def coordinator_sequence_window(
    next_inst, crnd, active
) -> tuple[jax.Array, ...]:
    """Oracle for kernels.coordinator.coordinator_sequence_window."""
    b = active.shape[0]
    inst = jnp.asarray(next_inst, jnp.int32) + jnp.arange(b, dtype=jnp.int32)
    msgtype = jnp.where(active.astype(bool), MSG_P2A, MSG_NOP).astype(jnp.int32)
    rnd = jnp.full((b,), jnp.asarray(crnd, jnp.int32), jnp.int32)
    vrnd = jnp.full((b,), NO_ROUND, jnp.int32)
    return msgtype, inst, rnd, vrnd, (jnp.asarray(next_inst, jnp.int32) + b)


def learner_quorum_window(
    quorum, vote_type, vote_vrnd, vote_val
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for kernels.learner.learner_quorum_window."""
    is_vote = vote_type == MSG_P2B
    masked = jnp.where(is_vote, vote_vrnd, NO_ROUND)
    win = jnp.max(masked, axis=0)
    agree = is_vote & (vote_vrnd == win[None, :])
    count = jnp.sum(agree.astype(jnp.int32), axis=0)
    deliver = (count >= jnp.asarray(quorum, jnp.int32)).astype(jnp.int32)
    first = agree & (jnp.cumsum(agree.astype(jnp.int32), axis=0) == 1)
    value = jnp.sum(first.astype(jnp.int32)[:, :, None] * vote_val, axis=0)
    return deliver, win, value


def wirepath_round(
    next_inst, crnd, quorum, alive,
    st_rnd, st_vrnd, st_val, ldel, linst, lval, values,
) -> tuple[jax.Array, ...]:
    """Oracle for kernels.wirepath.wirepath_round — delegates to the jnp
    fused round so oracle and system share one source of protocol truth."""
    b = values.shape[0]
    cstate = batched.CoordinatorState(
        next_inst=jnp.asarray(next_inst, jnp.int32),
        crnd=jnp.asarray(crnd, jnp.int32),
    )
    stack = AcceptorState(st_rnd, st_vrnd, st_val)
    lstate = batched.LearnerState(ldel, linst, lval)
    active = jnp.ones((b,), bool)
    _, stack, lstate, fresh, _, win, value = batched.fused_round(
        cstate, stack, lstate, values, active,
        jnp.asarray(alive).astype(bool), jnp.asarray(quorum, jnp.int32),
    )
    return (
        stack.rnd, stack.vrnd, stack.value,
        lstate.delivered, lstate.inst, lstate.value,
        fresh.astype(jnp.int32), win, value,
    )


def acceptor_vote_all_window(
    st_rnd, st_vrnd, st_val, base, alive, msgtype, msg_rnd, msg_val
) -> tuple[jax.Array, ...]:
    """Oracle for kernels.wirepath.acceptor_vote_all_window."""
    n = st_rnd.shape[1]
    b = msgtype.shape[0]
    inst = (jnp.asarray(base, jnp.int32) + jnp.arange(b, dtype=jnp.int32)) % n
    msgs = MsgBatch(
        msgtype=msgtype,
        inst=inst,
        rnd=msg_rnd,
        vrnd=jnp.full((b,), NO_ROUND, jnp.int32),
        swid=jnp.zeros((b,), jnp.int32),
        value=msg_val,
    )
    stack = AcceptorState(st_rnd, st_vrnd, st_val)
    stack, votes = batched.acceptor_phase2_all(
        stack, msgs, jnp.asarray(alive).astype(bool)
    )
    return (
        stack.rnd, stack.vrnd, stack.value,
        votes.msgtype, votes.rnd, votes.vrnd, votes.swid, votes.value,
    )


def digest(x: jax.Array) -> jax.Array:
    """Oracle for kernels.digest.digest (including padding semantics)."""
    flat = x.reshape(-1)
    bits = flat.view(jnp.int32) if flat.dtype != jnp.int32 else flat
    idx = jnp.arange(bits.shape[0], dtype=jnp.int32)
    return jnp.sum(bits * (idx * 2 + 1))
