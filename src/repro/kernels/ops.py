"""Jit'd wrappers exposing the Pallas kernels with the ``core.batched``
signatures, so the hardware dataplane (``core.api.HardwareDataplane``) can be
switched between the jnp engine and the kernels with one flag.

On a CPU backend the kernels execute in ``interpret=True`` mode — the
kernel body runs in Python for correctness validation; on a TPU backend
they compile to Mosaic.  ``INTERPRET`` follows the default backend.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.analysis.contracts import dataplane_contract
from repro.core import batched as _batched
from repro.core.batched import LearnerState
from repro.core.types import AcceptorState, CoordinatorState, MsgBatch

from . import acceptor as _acceptor
from . import coordinator as _coordinator
from . import digest as _digest
from . import learner as _learner
from . import ref as _ref
from . import wirepath as _wirepath

NO_ROUND = -1
INTERPRET = jax.default_backend() == "cpu"


@dataplane_contract(oracle=_batched.coordinator_sequence)
def coordinator_sequence(
    cstate: CoordinatorState, values: jax.Array, active: jax.Array
) -> tuple[CoordinatorState, MsgBatch]:
    """Kernel-backed drop-in for ``batched.coordinator_sequence``."""
    b = values.shape[0]
    msgtype, inst, rnd, vrnd, new_next = _coordinator.coordinator_sequence_window(
        cstate.next_inst, cstate.crnd, jnp.asarray(active), interpret=INTERPRET
    )
    out = MsgBatch(
        msgtype=msgtype,
        inst=inst,
        rnd=rnd,
        vrnd=vrnd,
        swid=jnp.zeros((b,), jnp.int32),
        value=values,
    )
    return CoordinatorState(next_inst=new_next, crnd=cstate.crnd), out


@dataplane_contract(oracle=_batched.acceptor_phase2, state_args=("astate",))
def acceptor_phase2(
    astate: AcceptorState, msgs: MsgBatch, aid: int | jax.Array = 0
) -> tuple[AcceptorState, MsgBatch]:
    """Kernel-backed drop-in for ``batched.acceptor_phase2``.

    Requires the contiguous-window invariant maintained by the sequencer:
    ``msgs.inst == base + iota(B)`` with ``base`` a multiple of the kernel
    batch block.  (The API layer always produces such batches.)
    """
    base = msgs.inst[0]
    (st_rnd, st_vrnd, st_val, vt, vr, vv, vs, vval) = (
        _acceptor.acceptor_phase2_window(
            astate.rnd,
            astate.vrnd,
            astate.value,
            base,
            jnp.asarray(aid, jnp.int32),
            msgs.msgtype,
            msgs.rnd,
            msgs.value,
            interpret=INTERPRET,
        )
    )
    votes = MsgBatch(
        msgtype=vt, inst=msgs.inst, rnd=vr, vrnd=vv, swid=vs, value=vval
    )
    return AcceptorState(st_rnd, st_vrnd, st_val), votes


@dataplane_contract(oracle=_batched.learner_quorum)
def learner_quorum(
    vote_msgtype: jax.Array,
    vote_inst: jax.Array,
    vote_vrnd: jax.Array,
    vote_value: jax.Array,
    quorum: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Kernel-backed drop-in for ``batched.learner_quorum``."""
    deliver, win, value = _learner.learner_quorum_window(
        jnp.int32(quorum),
        vote_msgtype,
        vote_vrnd,
        vote_value,
        interpret=INTERPRET,
    )
    inst = vote_inst[0]  # position-aligned batches: inst identical across A
    return deliver.astype(bool), inst, win, value


@dataplane_contract(
    oracle=_batched.fused_round,
    state_args=("stack", "lstate"),
    extra=("window_blocks",),
)
def fused_round(
    cstate: CoordinatorState,
    stack: AcceptorState,
    lstate: LearnerState,
    values: jax.Array,
    active: jax.Array,
    alive: jax.Array,
    quorum: int | jax.Array,
    reclaim_limit: jax.Array | None = None,
    *,
    window_blocks: int | None = None,
) -> tuple[CoordinatorState, AcceptorState, LearnerState,
           jax.Array, jax.Array, jax.Array, jax.Array]:
    """Kernel-backed drop-in for ``batched.fused_round`` — the whole Phase-2
    round in one ``pallas_call`` (DESIGN.md §3).

    ``active`` is accepted for signature parity but never reaches the device:
    sequenced NOP fillers vote identically to P2As, so on the wire path the
    active mask only matters to the application layer (which discards fillers
    by value).  ``window_blocks`` is the ring-block count the window needs
    (``core.plan.window_blocks``, from the host watermark mirror).
    ``reclaim_limit`` is the first instance the ring may NOT sequence into
    (snapshot watermark + N, DESIGN.md §9); ``None`` = no reclamation.
    """
    del active  # sequenced fillers vote like P2As; see docstring
    b = values.shape[0]
    (st_rnd, st_vrnd, st_val, ldel, linst, lval, fresh, win, value) = (
        _wirepath.wirepath_round(
            cstate.next_inst,
            cstate.crnd,
            jnp.asarray(quorum, jnp.int32),
            jnp.asarray(alive, jnp.int32),
            stack.rnd,
            stack.vrnd,
            stack.value,
            lstate.delivered,
            lstate.inst,
            lstate.value,
            values,
            reclaim_limit,
            window_blocks=window_blocks,
            interpret=INTERPRET,
        )
    )
    inst = cstate.next_inst + jnp.arange(b, dtype=jnp.int32)
    new_c = CoordinatorState(
        next_inst=cstate.next_inst + b, crnd=cstate.crnd
    )
    return (
        new_c,
        AcceptorState(st_rnd, st_vrnd, st_val),
        LearnerState(ldel, linst, lval),
        fresh != 0,
        inst,
        win,
        value,
    )


@dataplane_contract(
    oracle=_batched.multigroup_fused_round,
    state_args=("stack", "lstate"),
    extra=("group_block", "window_blocks"),
)
def multigroup_fused_round(
    cstate: CoordinatorState,   # leaves shaped (G,)
    stack: AcceptorState,       # leaves shaped (G, A, N[, V])
    lstate: LearnerState,       # leaves shaped (G, N[, V])
    values: jax.Array,          # int32[G, B, V]
    active: jax.Array,          # bool[G, B]
    alive: jax.Array,           # bool[G, A]
    quorum: int | jax.Array,
    enabled: jax.Array | None = None,
    reclaim_limit: jax.Array | None = None,  # int32[G]; None = no reclamation
    *,
    group_block: int = 1,
    window_blocks: int | None = None,
) -> tuple[CoordinatorState, AcceptorState, LearnerState,
           jax.Array, jax.Array, jax.Array, jax.Array]:
    """Kernel-backed drop-in for ``batched.multigroup_fused_round`` — G
    device-resident Paxos groups, one ``pallas_call`` (DESIGN.md §5).

    ``active`` never reaches the device for the same reason as in
    ``fused_round``.  ``group_block > 1`` folds groups into one grid step —
    legal only when the folded *enabled* groups' watermarks are in lockstep,
    which the ``MultiGroupDataplane`` checks against its host watermark
    mirrors; ``enabled`` (0/1 per group) marks frozen/vacant/idle groups so
    the kernel can hold them inert and fold over their divergent watermarks
    (DESIGN.md §7).  ``window_blocks`` as in ``fused_round``.
    """
    del active  # sequenced fillers vote like P2As; see fused_round
    b = values.shape[1]
    (st_rnd, st_vrnd, st_val, ldel, linst, lval, fresh, win, value) = (
        _wirepath.multigroup_wirepath_round(
            cstate.next_inst,
            cstate.crnd,
            jnp.asarray(quorum, jnp.int32),
            jnp.asarray(alive, jnp.int32),
            stack.rnd,
            stack.vrnd,
            stack.value,
            lstate.delivered,
            lstate.inst,
            lstate.value,
            values,
            None if enabled is None else jnp.asarray(enabled, jnp.int32),
            reclaim_limit,
            group_block=group_block,
            window_blocks=window_blocks,
            interpret=INTERPRET,
        )
    )
    inst = cstate.next_inst[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
    new_c = CoordinatorState(
        next_inst=cstate.next_inst + b, crnd=cstate.crnd
    )
    return (
        new_c,
        AcceptorState(st_rnd, st_vrnd, st_val),
        LearnerState(ldel, linst, lval),
        fresh != 0,
        inst,
        win,
        value,
    )


@dataplane_contract(
    oracle=None,
    state_args=("stack", "lstate"),
    reason=(
        "compositional entry with no standalone oracle: the jnp parity "
        "path is full-width batched.multigroup_fused_round over "
        "scatter-expanded cohort rows (tests/test_wirepath_parity.py)"
    ),
)
def cohort_fused_round(
    stack: AcceptorState,       # leaves shaped (G, A, N[, V])
    lstate: LearnerState,       # leaves shaped (G, N[, V])
    gsel: jax.Array,            # int32[NB]  selected group-block indices
    next_inst: jax.Array,       # int32[G]
    crnd: jax.Array,            # int32[G]
    alive: jax.Array,           # int32[G, A]
    quorum: int | jax.Array,
    values: jax.Array,          # int32[NB*GB, B, V]  compact cohort burst
    enabled: jax.Array,         # int32[G]  cohort membership mask
    reclaim_limit: jax.Array | None = None,  # int32[G]; None = no reclamation
    *,
    group_block: int = 1,
    window_blocks: int | None = None,
) -> tuple[AcceptorState, LearnerState, jax.Array, jax.Array, jax.Array]:
    """Cohort-compacted fused round (DESIGN.md §8): the grid visits only the
    group blocks named by ``gsel``, so a dispatch costs what its cohort
    costs — not the full capacity G.  Stateless with respect to the
    coordinator: the dataplane advances its own watermark mirrors for the
    cohort members (it must mask non-members anyway).

    Returns ``(stack', lstate', fresh[C, B], win[C, B], value[C, B, V])``
    with ``C = NB * group_block`` compact rows in ``gsel``-block order.
    """
    (st_rnd, st_vrnd, st_val, ldel, linst, lval, fresh, win, value) = (
        _wirepath.cohort_wirepath_round(
            jnp.asarray(gsel, jnp.int32),
            next_inst,
            crnd,
            jnp.asarray(quorum, jnp.int32),
            jnp.asarray(alive, jnp.int32),
            stack.rnd,
            stack.vrnd,
            stack.value,
            lstate.delivered,
            lstate.inst,
            lstate.value,
            values,
            jnp.asarray(enabled, jnp.int32),
            reclaim_limit,
            group_block=group_block,
            window_blocks=window_blocks,
            interpret=INTERPRET,
        )
    )
    return (
        AcceptorState(st_rnd, st_vrnd, st_val),
        LearnerState(ldel, linst, lval),
        fresh != 0,
        win,
        value,
    )


@dataplane_contract(
    oracle=_batched.packed_multigroup_round,
    state_args=("stack", "lstate"),
    extra=("block_b", "window_blocks"),
)
def packed_shard_round(
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
    *,
    block_b: int | None = None,
    window_blocks: int | None = None,
) -> tuple[AcceptorState, LearnerState, jax.Array, jax.Array, jax.Array]:
    """Packed ragged-shard round (DESIGN.md §13): ``C`` uniform lanes, each
    routed to its resident slab row by the ``segids`` prefetch table, so a
    shard's dispatch costs what its enabled lanes cost — not the full
    ``Gl``-row slab.  Coordinator-stateless like ``cohort_fused_round``
    (the dataplane advances its own watermark mirrors per lane).

    Returns ``(stack', lstate', fresh[C, B], win[C, B], value[C, B, V])``
    in packed lane order; pads return all-inert rows.
    """
    if block_b is None:
        block_b = _wirepath.DEFAULT_BLOCK_B
    (st_rnd, st_vrnd, st_val, ldel, linst, lval, fresh, win, value) = (
        _wirepath.packed_shard_round(
            jnp.asarray(segids, jnp.int32),
            next_inst,
            crnd,
            jnp.asarray(quorum, jnp.int32),
            jnp.asarray(alive, jnp.int32),
            stack.rnd,
            stack.vrnd,
            stack.value,
            lstate.delivered,
            lstate.inst,
            lstate.value,
            values,
            jnp.asarray(enabled, jnp.int32),
            reclaim_limit,
            block_b=block_b,
            window_blocks=window_blocks,
            interpret=INTERPRET,
        )
    )
    return (
        AcceptorState(st_rnd, st_vrnd, st_val),
        LearnerState(ldel, linst, lval),
        fresh != 0,
        win,
        value,
    )


@dataplane_contract(
    oracle=_batched.persistent_multigroup_rounds,
    state_args=("stack", "lstate"),
    extra=("gsel", "wni", "wen", "crnd", "group_block", "block_b"),
    oracle_extra=("cstate", "active", "enabled_rounds"),
    strict_order=False,
)
def persistent_cohort_rounds(
    stack: AcceptorState,       # leaves shaped (G, A, N[, V])
    lstate: LearnerState,       # leaves shaped (G, N[, V])
    gsel: jax.Array,            # int32[NB]  selected group-block indices
    wni: jax.Array,             # int32[K, G]  per-round window bases
    wen: jax.Array,             # int32[K, G]  per-round participation
    crnd: jax.Array,            # int32[G]
    alive: jax.Array,           # int32[G, A]
    quorum: int | jax.Array,
    values: jax.Array,          # int32[K, NB*GB, B, V]  compact wave values
    reclaim_limit: jax.Array | None = None,  # int32[G]; None = no reclamation
    *,
    group_block: int = 1,
    block_b: int | None = None,
) -> tuple[AcceptorState, LearnerState, jax.Array, jax.Array, jax.Array]:
    """Persistent K-round wave dispatch (DESIGN.md §11): the whole chunk
    wave stays device-resident and syncs back to host once per K rounds.
    Coordinator-stateless like ``cohort_fused_round`` — the dataplane walks
    its own watermark mirrors from the same ``wni``/``wen`` descriptor.

    Returns ``(stack', lstate', fresh[K, C, B], win[K, C, B],
    value[K, C, B, V])`` with ``C = NB * group_block`` compact rows.
    """
    if block_b is None:
        block_b = _wirepath.DEFAULT_BLOCK_B
    (st_rnd, st_vrnd, st_val, ldel, linst, lval, fresh, win, value) = (
        _wirepath.persistent_wirepath_round(
            jnp.asarray(gsel, jnp.int32),
            jnp.asarray(wni, jnp.int32),
            jnp.asarray(wen, jnp.int32),
            crnd,
            jnp.asarray(quorum, jnp.int32),
            jnp.asarray(alive, jnp.int32),
            stack.rnd,
            stack.vrnd,
            stack.value,
            lstate.delivered,
            lstate.inst,
            lstate.value,
            values,
            reclaim_limit,
            block_b=block_b,
            group_block=group_block,
            interpret=INTERPRET,
        )
    )
    return (
        AcceptorState(st_rnd, st_vrnd, st_val),
        LearnerState(ldel, linst, lval),
        fresh != 0,
        win,
        value,
    )


@dataplane_contract(oracle=_batched.acceptor_phase2_all, state_args=("stack",))
def acceptor_phase2_all(
    stack: AcceptorState, msgs: MsgBatch, alive: jax.Array
) -> tuple[AcceptorState, MsgBatch]:
    """Kernel-backed drop-in for ``batched.acceptor_phase2_all``.

    Requires the contiguous-window invariant (``msgs.inst == base + iota(B)``
    with ``base`` and B multiples of the ring block); the API layer takes the
    jnp scatter path, and counts it, when it cannot guarantee that.
    """
    base = msgs.inst[0]
    (st_rnd, st_vrnd, st_val, vt, vr, vv, vs, vval) = (
        _wirepath.acceptor_vote_all_window(
            stack.rnd,
            stack.vrnd,
            stack.value,
            base,
            jnp.asarray(alive, jnp.int32),
            msgs.msgtype,
            msgs.rnd,
            msgs.value,
            interpret=INTERPRET,
        )
    )
    votes = MsgBatch(
        msgtype=vt,
        inst=jnp.broadcast_to(msgs.inst[None, :], vt.shape),
        rnd=vr,
        vrnd=vv,
        swid=vs,
        value=vval,
    )
    return AcceptorState(st_rnd, st_vrnd, st_val), votes


@dataplane_contract(oracle=_ref.digest)
def digest(x: jax.Array) -> jax.Array:
    return _digest.digest(x, interpret=INTERPRET)


@dataplane_contract(
    oracle=None,
    reason=(
        "leaf-wise composition of ``digest``: the jnp oracle is "
        "kernels.ref.digest applied per flattened leaf, folded with the "
        "same mixing constant (tests/test_digest.py pins parity)"
    ),
)
def tree_digest(tree) -> jax.Array:
    return _digest.tree_digest(tree, interpret=INTERPRET)
