"""Batched multi-instance Paxos dataplane in JAX.

This is the jnp-level "hardware" implementation of the coordinator / acceptor
/ learner-quorum logic: every function processes a *batch* of Paxos headers
(``MsgBatch``) in one shot.  The Pallas kernels in ``repro.kernels`` implement
the same functions with explicit VMEM tiling; ``kernels/ref.py`` re-exports
these as the oracles.

Semantics notes
---------------
* ``coordinator_sequence`` assigns a contiguous instance window to each batch
  (monotonic sequencer).  Slots in a batch therefore hit *distinct* acceptor
  ring slots, which makes the vectorized scatter in ``acceptor_phase2`` exact.
* For adversarial traffic (recovery, duplicated instances inside one batch)
  use ``acceptor_sequential`` — a ``lax.scan`` with exact one-message-at-a-time
  semantics.  Tests check that on distinct-slot batches both paths agree.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .types import (
    MSG_NOP,
    MSG_P1A,
    MSG_P1B,
    MSG_P2A,
    MSG_P2B,
    MSG_REJECT,
    AcceptorState,
    CoordinatorState,
    MsgBatch,
)

NO_ROUND = jnp.int32(-1)


# ---------------------------------------------------------------------------
# Coordinator (sequencer)
# ---------------------------------------------------------------------------
def coordinator_sequence(
    cstate: CoordinatorState, values: jax.Array, active: jax.Array
) -> tuple[CoordinatorState, MsgBatch]:
    """Bind a batch of proposals to a contiguous window of instances.

    Inactive slots still consume an instance and carry a NOP marker — they are
    decided and discarded by the application layer (the paper's no-op values).
    This preserves window contiguity, the property the acceptor fast path and
    the Pallas kernel exploit.
    """
    b = values.shape[0]
    inst = cstate.next_inst + jnp.arange(b, dtype=jnp.int32)
    msgtype = jnp.where(active, MSG_P2A, MSG_NOP).astype(jnp.int32)
    out = MsgBatch(
        msgtype=msgtype,
        inst=inst,
        rnd=jnp.full((b,), cstate.crnd, jnp.int32),
        vrnd=jnp.full((b,), NO_ROUND, jnp.int32),
        swid=jnp.zeros((b,), jnp.int32),
        value=values,
    )
    new = CoordinatorState(next_inst=cstate.next_inst + b, crnd=cstate.crnd)
    return new, out


# ---------------------------------------------------------------------------
# Acceptor — vectorized fast path (distinct ring slots per batch)
# ---------------------------------------------------------------------------
def acceptor_phase2(
    astate: AcceptorState, msgs: MsgBatch, aid: int | jax.Array = 0
) -> tuple[AcceptorState, MsgBatch]:
    """Vote on a batch of P2A requests against the instance ring.

    accept iff msgtype==P2A and msg.rnd >= promised rnd of the slot.
    NOP slots pass through as NOPs (they are *not* votes).
    """
    n = astate.n_instances
    slots = msgs.inst % n
    cur_rnd = astate.rnd[slots]
    is_p2a = (msgs.msgtype == MSG_P2A) | (msgs.msgtype == MSG_NOP)
    # NOP slots are sequenced instances carrying the no-op value: acceptors
    # still vote so the instance is decided (and later discarded upstream).
    accept = is_p2a & (msgs.rnd >= cur_rnd)

    new_rnd = jnp.where(accept, msgs.rnd, cur_rnd)
    new_vrnd = jnp.where(accept, msgs.rnd, astate.vrnd[slots])
    new_val = jnp.where(accept[:, None], msgs.value, astate.value[slots])

    astate = AcceptorState(
        rnd=astate.rnd.at[slots].set(new_rnd, mode="drop"),
        vrnd=astate.vrnd.at[slots].set(new_vrnd, mode="drop"),
        value=astate.value.at[slots].set(new_val, mode="drop"),
    )
    votes = MsgBatch(
        msgtype=jnp.where(accept, MSG_P2B, MSG_REJECT).astype(jnp.int32),
        inst=msgs.inst,
        rnd=jnp.where(accept, msgs.rnd, cur_rnd),
        vrnd=jnp.where(accept, msgs.rnd, astate.vrnd[slots]),
        swid=jnp.full_like(msgs.swid, aid),
        value=jnp.where(accept[:, None], msgs.value, 0),
    )
    return astate, votes


def acceptor_phase1(
    astate: AcceptorState, msgs: MsgBatch, aid: int | jax.Array = 0
) -> tuple[AcceptorState, MsgBatch]:
    """Promise on a batch of P1A prepares (recovery / takeover path)."""
    n = astate.n_instances
    slots = msgs.inst % n
    cur_rnd = astate.rnd[slots]
    cur_vrnd = astate.vrnd[slots]
    cur_val = astate.value[slots]
    is_p1a = msgs.msgtype == MSG_P1A
    promise = is_p1a & (msgs.rnd > cur_rnd)

    astate = AcceptorState(
        rnd=astate.rnd.at[slots].set(jnp.where(promise, msgs.rnd, cur_rnd), mode="drop"),
        vrnd=astate.vrnd,
        value=astate.value,
    )
    out = MsgBatch(
        msgtype=jnp.where(promise, MSG_P1B, MSG_REJECT).astype(jnp.int32),
        inst=msgs.inst,
        rnd=jnp.where(promise, msgs.rnd, cur_rnd),
        vrnd=cur_vrnd,
        swid=jnp.full_like(msgs.swid, aid),
        value=cur_val,
    )
    return astate, out


# ---------------------------------------------------------------------------
# Acceptor array — all 2f+1 acceptors in one dispatch (SoA stacked state)
# ---------------------------------------------------------------------------
def acceptor_phase2_all(
    stack: AcceptorState, msgs: MsgBatch, alive: jax.Array
) -> tuple[AcceptorState, MsgBatch]:
    """Phase-2 vote of the *whole* acceptor array on one P2A batch.

    ``stack`` holds the A register files stacked on a leading axis; ``alive``
    is a bool[A] runtime mask.  Dead acceptors neither vote (their rows come
    back MSG_REJECT) nor mutate their register file — exactly the semantics
    of a crashed switch: its BRAM is frozen and it emits nothing.

    Shares ``acceptor_phase2``'s vectorized-scatter precondition: batch
    positions must hit *distinct* ring slots (``inst % N`` pairwise
    distinct), or slot updates race.  Use ``acceptor_sequential`` for
    adversarial duplicate-slot traffic.

    One dispatch replaces the historical per-acceptor Python loop (which
    rewrote the full stacked state with ``.at[aid].set`` per acceptor).
    Returns (stack', votes) with every vote field shaped [A, ...].
    """
    a, n, v = stack.value.shape
    slots = msgs.inst % n
    cur_rnd = stack.rnd[:, slots]                               # [A, B]
    is_p2a = (msgs.msgtype == MSG_P2A) | (msgs.msgtype == MSG_NOP)
    # a crashed acceptor accepts nothing: its scatter writes its slots back
    # unchanged, and its vote row is exactly what a pure rejecter would emit
    # (so the kernel path can reproduce it without special cases)
    accept = is_p2a & (msgs.rnd >= cur_rnd) & alive[:, None]   # [A, B]
    new_rnd = jnp.where(accept, msgs.rnd, cur_rnd)
    new_vrnd = jnp.where(accept, msgs.rnd, stack.vrnd[:, slots])
    # all A value rings as (A*V, N) rows, updated by one scatter along N: a
    # per-acceptor scatter made XLA copy the whole ring into a layout that
    # pads (A, V) to a (4, 128) tile, 8 GiB per round at G=64 on a TPU
    rows = jnp.swapaxes(stack.value, 1, 2).reshape(a * v, n)
    new_val = jnp.where(
        accept[:, None, :], msgs.value.T, rows[:, slots].reshape(a, v, -1)
    )
    rows = rows.at[:, slots].set(new_val.reshape(a * v, -1), mode="drop")
    stack = AcceptorState(
        rnd=stack.rnd.at[:, slots].set(new_rnd, mode="drop"),
        vrnd=stack.vrnd.at[:, slots].set(new_vrnd, mode="drop"),
        value=jnp.swapaxes(rows.reshape(a, v, n), 1, 2),
    )
    votes = MsgBatch(
        msgtype=jnp.where(accept, MSG_P2B, MSG_REJECT).astype(jnp.int32),
        inst=jnp.broadcast_to(msgs.inst, accept.shape),
        rnd=new_rnd,
        vrnd=new_vrnd,
        swid=jnp.broadcast_to(
            jnp.arange(a, dtype=msgs.swid.dtype)[:, None], accept.shape
        ),
        value=jnp.where(accept[:, :, None], msgs.value, 0),
    )
    return stack, votes


def acceptor_phase1_all(
    stack: AcceptorState, msgs: MsgBatch, alive: jax.Array
) -> tuple[AcceptorState, MsgBatch]:
    """Phase-1 promise of the whole acceptor array (recovery/takeover path)."""
    a = stack.rnd.shape[0]

    def prep_one(st, aid, alv):
        # a crashed acceptor receives no requests: it promises nothing and
        # its register file stays as it was
        unseen = msgs.replace(msgtype=jnp.where(alv, msgs.msgtype, MSG_REJECT))
        return acceptor_phase1(st, unseen, aid=aid)

    return jax.vmap(prep_one)(stack, jnp.arange(a), alive)


# ---------------------------------------------------------------------------
# Acceptor — exact sequential semantics (any batch, incl. duplicate slots)
# ---------------------------------------------------------------------------
def acceptor_sequential(
    astate: AcceptorState, msgs: MsgBatch, aid: int | jax.Array = 0
) -> tuple[AcceptorState, MsgBatch]:
    """One-message-at-a-time semantics via lax.scan (recovery / adversarial)."""

    def step(state: AcceptorState, m):
        msgtype, inst, rnd, vrnd, swid, value = m
        n = state.n_instances
        slot = inst % n
        cur_rnd = state.rnd[slot]
        cur_vrnd = state.vrnd[slot]
        cur_val = state.value[slot]

        is_p2 = (msgtype == MSG_P2A) | (msgtype == MSG_NOP)
        is_p1 = msgtype == MSG_P1A
        accept = is_p2 & (rnd >= cur_rnd)
        promise = is_p1 & (rnd > cur_rnd)

        upd_rnd = jnp.where(accept | promise, rnd, cur_rnd)
        upd_vrnd = jnp.where(accept, rnd, cur_vrnd)
        upd_val = jnp.where(accept, value, cur_val)
        state = AcceptorState(
            rnd=state.rnd.at[slot].set(upd_rnd),
            vrnd=state.vrnd.at[slot].set(upd_vrnd),
            value=state.value.at[slot].set(upd_val),
        )
        out_type = jnp.where(
            accept, MSG_P2B, jnp.where(promise, MSG_P1B, MSG_REJECT)
        ).astype(jnp.int32)
        out = (
            out_type,
            inst,
            jnp.where(accept | promise, rnd, cur_rnd),
            jnp.where(accept, rnd, cur_vrnd),
            jnp.full_like(swid, aid),
            jnp.where(is_p1, cur_val, jnp.where(accept, value, jnp.zeros_like(value))),
        )
        return state, out

    ms = (msgs.msgtype, msgs.inst, msgs.rnd, msgs.vrnd, msgs.swid, msgs.value)
    astate, outs = jax.lax.scan(step, astate, ms)
    return astate, MsgBatch(*outs)


# ---------------------------------------------------------------------------
# Learner — quorum over stacked votes
# ---------------------------------------------------------------------------
def learner_quorum(
    vote_msgtype: jax.Array,   # int32[A, B]
    vote_inst: jax.Array,      # int32[A, B]
    vote_vrnd: jax.Array,      # int32[A, B]
    vote_value: jax.Array,     # int32[A, B, V]
    quorum: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Position-aligned quorum count over the acceptor axis.

    Votes arriving from the A acceptors for the same P2A batch are aligned by
    batch position.  deliver[b] iff >= quorum acceptors voted (P2B) with the
    same vrnd.  Value is taken from any acceptor voting the winning vrnd
    (Paxos guarantees value uniqueness per (inst, rnd)).
    """
    is_vote = vote_msgtype == MSG_P2B                       # [A, B]
    # winning round = max vrnd among votes (NO_ROUND where none)
    vrnd_masked = jnp.where(is_vote, vote_vrnd, NO_ROUND)
    win_vrnd = jnp.max(vrnd_masked, axis=0)                 # [B]
    agree = is_vote & (vote_vrnd == win_vrnd[None, :])      # [A, B]
    count = jnp.sum(agree.astype(jnp.int32), axis=0)        # [B]
    deliver = count >= quorum                               # [B]

    # first acceptor index voting the winning round
    first = jnp.argmax(agree, axis=0)                       # [B]
    b = vote_inst.shape[1]
    cols = jnp.arange(b)
    inst = vote_inst[first, cols]
    value = vote_value[first, cols]
    return deliver, inst, win_vrnd, value


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LearnerState:
    """Dedup memory over the instance ring: delivered mask (0/1 int32, the
    kernel-native layout), the absolute instance last decided into each slot,
    and the decided value.

    Tracking the absolute ``inst`` per slot makes the dedup *ring-correct*:
    re-delivery of the same instance is suppressed, but a later instance
    reusing the slot after wraparound is fresh again (bounded memory, paper
    Table 3's 65,535-instance BRAM).
    """

    delivered: jax.Array  # int32[N]  0/1 mask
    inst: jax.Array       # int32[N]  absolute instance decided into the slot
    value: jax.Array      # int32[N, V]

    def tree_flatten(self):
        return ((self.delivered, self.inst, self.value), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def init(cls, n_instances: int, value_words: int) -> "LearnerState":
        return cls(
            delivered=jnp.zeros((n_instances,), jnp.int32),
            inst=jnp.full((n_instances,), -1, jnp.int32),
            value=jnp.zeros((n_instances, value_words), jnp.int32),
        )


def learner_update(
    lstate: LearnerState,
    deliver: jax.Array,
    inst: jax.Array,
    value: jax.Array,
) -> tuple[LearnerState, jax.Array]:
    """Record deliveries; returns mask of *fresh* (not duplicate) deliveries."""
    n = lstate.delivered.shape[0]
    slots = inst % n
    dup = (lstate.delivered[slots] != 0) & (lstate.inst[slots] == inst)
    fresh = deliver & ~dup
    lstate = LearnerState(
        delivered=lstate.delivered.at[slots].set(
            lstate.delivered[slots] | deliver.astype(jnp.int32), mode="drop"
        ),
        inst=lstate.inst.at[slots].set(
            jnp.where(fresh, inst, lstate.inst[slots]), mode="drop"
        ),
        value=lstate.value.at[slots].set(
            jnp.where(fresh[:, None], value, lstate.value[slots]), mode="drop"
        ),
    )
    return lstate, fresh


# ---------------------------------------------------------------------------
# Fused wire path — one Phase-2 round, sequencer -> acceptor array -> learner
# ---------------------------------------------------------------------------
def fused_round(
    cstate: CoordinatorState,
    stack: AcceptorState,
    lstate: LearnerState,
    values: jax.Array,    # int32[B, V]
    active: jax.Array,    # bool[B]
    alive: jax.Array,     # bool[A]
    quorum: int | jax.Array,
    reclaim_limit: jax.Array | None = None,  # int32[]; None = no reclamation
) -> tuple[CoordinatorState, AcceptorState, LearnerState,
           jax.Array, jax.Array, jax.Array, jax.Array]:
    """The CAANS wire path as one jnp program: coordinator sequencing, the
    whole acceptor array's Phase-2 vote, learner quorum count, and ring-dedup
    update — no host round-trips between the stages.

    This is the semantic oracle (and CPU fallback) for the Pallas megakernel
    ``repro.kernels.wirepath.wirepath_round``; the two must agree bit-for-bit
    (DESIGN.md §3).  ``reclaim_limit`` is the first instance the ring may NOT
    sequence into (snapshot watermark + N, DESIGN.md §9): lanes at or past it
    are presented at NO_ROUND so every acceptor rejects them — the oracle of
    the kernel's reclamation permit gate.  Returns
    ``(cstate', stack', lstate', fresh[B], inst[B], win_vrnd[B], value[B,V])``.
    """
    cstate, p2a = coordinator_sequence(cstate, values, active)
    if reclaim_limit is not None:
        permit = p2a.inst < jnp.asarray(reclaim_limit, jnp.int32)
        p2a = p2a.replace(rnd=jnp.where(permit, p2a.rnd, NO_ROUND))
    stack, votes = acceptor_phase2_all(stack, p2a, alive)
    deliver, inst, win, value = learner_quorum(
        votes.msgtype, votes.inst, votes.vrnd, votes.value, quorum
    )
    lstate, fresh = learner_update(lstate, deliver, inst, value)
    return cstate, stack, lstate, fresh, inst, win, value


# ---------------------------------------------------------------------------
# Multi-group wire path — G independent Paxos groups, one dispatch
# ---------------------------------------------------------------------------
def multigroup_fused_round(
    cstate: CoordinatorState,   # leaves shaped (G,)
    stack: AcceptorState,       # leaves shaped (G, A, N[, V])
    lstate: LearnerState,       # leaves shaped (G, N[, V])
    values: jax.Array,          # int32[G, B, V]
    active: jax.Array,          # bool[G, B]
    alive: jax.Array,           # bool[G, A]
    quorum: int | jax.Array,
    enabled: jax.Array | None = None,        # 0/1 per group; None = all
    reclaim_limit: jax.Array | None = None,  # int32[G]; None = no reclamation
) -> tuple[CoordinatorState, AcceptorState, LearnerState,
           jax.Array, jax.Array, jax.Array, jax.Array]:
    """``fused_round`` vmapped over a leading group axis: G device-resident
    Paxos groups advance one Phase-2 round in a single jnp program.

    Groups are fully independent — per-group sequencer watermark and round,
    per-group acceptor rings, per-group learner ring and liveness row — so
    this is bit-identical to running ``fused_round`` per group in a loop.
    It is the semantic oracle (and CPU fallback) for the Pallas megakernel
    ``repro.kernels.wirepath.multigroup_wirepath_round`` (DESIGN.md §5).
    ``reclaim_limit`` carries each group's reclamation limit (DESIGN.md §9).

    ``enabled`` (0/1 per group) holds disabled groups inert exactly as the
    kernel path does: a disabled group is presented at NO_ROUND so every
    acceptor rejects its slots.  Like the kernel wrapper, the returned
    coordinator watermark still advances for every group — callers that mix
    enabled/disabled groups correct the watermark with their own
    ``jnp.where(enabled, ...)`` (see ``persistent_multigroup_rounds``).
    Returns the ``fused_round`` tuple with every output grown a (G,) axis.
    """
    if enabled is not None:
        cstate = CoordinatorState(
            next_inst=cstate.next_inst,
            crnd=jnp.where(
                jnp.asarray(enabled) != 0, cstate.crnd, NO_ROUND
            ),
        )
    if reclaim_limit is None:
        return jax.vmap(fused_round, in_axes=(0, 0, 0, 0, 0, 0, None))(
            cstate, stack, lstate, values, active, alive, quorum
        )
    return jax.vmap(fused_round, in_axes=(0, 0, 0, 0, 0, 0, None, 0))(
        cstate, stack, lstate, values, active, alive, quorum,
        jnp.asarray(reclaim_limit, jnp.int32),
    )


def persistent_multigroup_rounds(
    cstate: CoordinatorState,   # leaves shaped (G,)
    stack: AcceptorState,       # leaves shaped (G, A, N[, V])
    lstate: LearnerState,       # leaves shaped (G, N[, V])
    values: jax.Array,          # int32[K, G, B, V]
    active: jax.Array,          # bool[K, G, B]
    alive: jax.Array,           # bool[G, A]
    quorum: int | jax.Array,
    enabled_rounds: jax.Array | None = None,  # bool/int32[K, G]; None = all
    reclaim_limit: jax.Array | None = None,   # int32[G]; None = no reclamation
) -> tuple[CoordinatorState, AcceptorState, LearnerState,
           jax.Array, jax.Array, jax.Array, jax.Array]:
    """K Phase-2 rounds unrolled in ONE jnp program: the bit-exact oracle of
    the persistent wave kernel ``kernels.wirepath.persistent_wirepath_round``
    (DESIGN.md §11).

    Round ``k`` runs ``multigroup_fused_round`` on ``values[k]`` with the
    per-round participation mask ``enabled_rounds[k]`` applied exactly as
    the dataplane applies ``enabled`` to a single-round dispatch: a group
    sitting the round out is presented at NO_ROUND (its acceptors reject
    every slot) and its watermark does not advance — so the whole wave is
    bit-identical to K sequential single-round dispatches by construction.
    ``K`` is a trace-time constant (the leading axis of ``values``); the
    Python loop unrolls under jit, so the wave still costs one dispatch.

    Returns ``(cstate', stack', lstate', fresh[K, G, B], inst[K, G, B],
    win_vrnd[K, G, B], value[K, G, B, V])``.
    """
    k = values.shape[0]
    freshes, insts, wins, vals = [], [], [], []
    for r in range(k):
        if enabled_rounds is None:
            en = None
            eff = cstate
        else:
            en = jnp.asarray(enabled_rounds[r]) != 0
            eff = CoordinatorState(
                next_inst=cstate.next_inst,
                crnd=jnp.where(en, cstate.crnd, NO_ROUND),
            )
        new_c, stack, lstate, fresh, inst, win, value = multigroup_fused_round(
            eff, stack, lstate, values[r], active[r], alive, quorum,
            reclaim_limit=reclaim_limit,
        )
        if en is None:
            cstate = CoordinatorState(
                next_inst=new_c.next_inst, crnd=cstate.crnd
            )
        else:
            cstate = CoordinatorState(
                next_inst=jnp.where(
                    en, new_c.next_inst, cstate.next_inst
                ),
                crnd=cstate.crnd,
            )
        freshes.append(fresh)
        insts.append(inst)
        wins.append(win)
        vals.append(value)
    return (
        cstate, stack, lstate,
        jnp.stack(freshes), jnp.stack(insts), jnp.stack(wins),
        jnp.stack(vals),
    )


def packed_multigroup_round(
    stack: AcceptorState,       # leaves shaped (Gl, A, N[, V])
    lstate: LearnerState,       # leaves shaped (Gl, N[, V])
    segids: jax.Array,          # int32[C]  per-lane slab row (0..Gl)
    next_inst: jax.Array,       # int32[C]  per-lane window base
    crnd: jax.Array,            # int32[C]  per-lane coordinator round
    alive: jax.Array,           # int32[C, A]  per-lane liveness row
    quorum: int | jax.Array,
    values: jax.Array,          # int32[C, B, V]  packed burst values
    enabled: jax.Array,         # int32[C]  0 marks a pad lane
    reclaim_limit: jax.Array | None = None,  # int32[C]; None = no reclamation
) -> tuple[AcceptorState, LearnerState, jax.Array, jax.Array, jax.Array]:
    """Bit-exact jnp oracle of the packed ragged-shard kernel
    ``kernels.wirepath.packed_shard_round`` (DESIGN.md §13).

    ``C`` packed lanes each serve slab row ``segids[j]`` of one shard's
    ``(Gl, ...)`` state with their own per-lane scalars.  Enabled lanes must
    name pairwise-distinct rows (the caller packs one lane per resident
    enabled group); pad lanes (``enabled == 0``) ride inert and write
    nothing back.  Gather the lanes' rows, run ``fused_round`` vmapped over
    the lane axis, scatter enabled lanes' rows back (pads scattered into a
    dropped trash row) — identical arithmetic to the kernel's routed grid.

    Returns ``(stack', lstate', fresh[C, B], win_vrnd[C, B],
    value[C, B, V])`` with the state outputs full-slab ``(Gl, ...)``.
    """
    gl = stack.rnd.shape[0]
    seg = jnp.asarray(segids, jnp.int32).reshape((-1,))
    c = seg.shape[0]
    en = jnp.asarray(enabled, jnp.int32).reshape((c,)) != 0
    cr = jnp.where(en, jnp.asarray(crnd, jnp.int32).reshape((c,)), NO_ROUND)
    cstate = CoordinatorState(
        next_inst=jnp.asarray(next_inst, jnp.int32).reshape((c,)), crnd=cr
    )
    lane_stack = jax.tree_util.tree_map(lambda x: x[seg], stack)
    lane_lstate = jax.tree_util.tree_map(lambda x: x[seg], lstate)
    active = jnp.ones(values.shape[:2], bool)
    al = jnp.asarray(alive).reshape((c, -1)) != 0
    if reclaim_limit is None:
        _c, lane_stack, lane_lstate, fresh, _inst, win, value = jax.vmap(
            fused_round, in_axes=(0, 0, 0, 0, 0, 0, None)
        )(cstate, lane_stack, lane_lstate, values, active, al, quorum)
    else:
        _c, lane_stack, lane_lstate, fresh, _inst, win, value = jax.vmap(
            fused_round, in_axes=(0, 0, 0, 0, 0, 0, None, 0)
        )(
            cstate, lane_stack, lane_lstate, values, active, al, quorum,
            jnp.asarray(reclaim_limit, jnp.int32).reshape((c,)),
        )
    # scatter lanes back to their slab rows; pads land in a dropped trash
    # row (their lane state is bit-unchanged anyway — NO_ROUND rejects all)
    tgt = jnp.where(en, seg, gl)

    def scat(full: jax.Array, lanes: jax.Array) -> jax.Array:
        return full.at[tgt].set(lanes, mode="drop")

    stack = jax.tree_util.tree_map(scat, stack, lane_stack)
    lstate = jax.tree_util.tree_map(scat, lstate, lane_lstate)
    return stack, lstate, fresh, win, value


def init_multigroup_state(
    n_groups: int, n_acceptors: int, n_instances: int, value_words: int,
    sharding: jax.sharding.Sharding | None = None,
) -> tuple[CoordinatorState, AcceptorState, LearnerState]:
    """Freshly initialized (G,)-stacked coordinator/acceptor/learner state.

    With ``sharding`` the acceptor and learner slabs are built in place,
    each device writing only its own part: slabs that fit the mesh need not
    fit one device."""
    cstate = CoordinatorState(
        next_inst=jnp.zeros((n_groups,), jnp.int32),
        crnd=jnp.zeros((n_groups,), jnp.int32),
    )

    def slabs():
        one = AcceptorState.init(n_instances, value_words)
        stack = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_groups, n_acceptors) + x.shape).copy(),
            one,
        )
        lstate = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape).copy(),
            LearnerState.init(n_instances, value_words),
        )
        return stack, lstate

    if sharding is None:
        stack, lstate = slabs()
    else:
        stack, lstate = jax.jit(slabs, out_shardings=sharding)()
    return cstate, stack, lstate
