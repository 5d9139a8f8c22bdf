"""In-fabric consensus: the whole Paxos Phase-2 round inside one shard_map.

This is the TPU analogue of the paper's central move — consensus logic
executing *on the interconnect* rather than in host software.  Acceptors are
shards of a device-mesh axis; a consensus round is one compiled collective
program:

    1. proposers (one per shard) contribute their local proposal batch,
    2. all_gather over the acceptor axis  == proposer->coordinator traffic,
    3. deterministic replicated sequencer == the coordinator,
    4. local acceptor vote (Pallas kernel / jnp fast path),
    5. psum of agree-bits over the axis  == acceptor->learner vote traffic,
    6. local quorum decision — every shard deterministically learns the
       decided values (every device is a learner).

No host round-trip happens anywhere in the round: "consensus messages travel
fewer hops", at ICI speed.  Acceptor failure is modelled by an ``alive`` mask
(a dead acceptor's votes never count); the round still decides while a quorum
(f+1 of 2f+1) lives.
"""
from __future__ import annotations

from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import batched
from .types import MSG_P2B, AcceptorState, CoordinatorState

NO_ROUND = jnp.int32(-1)


def _shard_map(
    f: Callable[..., Any],
    mesh: jax.sharding.Mesh,
    in_specs: Any,
    out_specs: Any,
) -> Callable[..., Any]:
    """``jax.shard_map`` with replication checking off: the replicated
    outputs here are replicated by construction (psum / identical
    sequencing), which the checker cannot always prove."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def consensus_round(
    astate: AcceptorState,
    cstate: CoordinatorState,
    values: jax.Array,        # int32[b_local, V]   local proposals (sharded)
    active: jax.Array,        # bool [b_local]
    alive: jax.Array,         # bool []             this acceptor is alive
    *,
    axis: str,
    quorum: int,
) -> tuple[AcceptorState, CoordinatorState, jax.Array, jax.Array, jax.Array]:
    """One in-fabric consensus round (runs *inside* shard_map).

    Returns (astate', cstate', decided_mask[B], inst[B], value[B, V]) with
    B = b_local * n_acceptors (the gathered global batch), identical on every
    shard.
    """
    my_idx = jax.lax.axis_index(axis)

    # (2) proposers -> coordinator: gather proposals from every shard.
    all_values = jax.lax.all_gather(values, axis, tiled=True)    # [B, V]
    all_active = jax.lax.all_gather(active, axis, tiled=True)    # [B]

    # (3) replicated deterministic sequencer (the coordinator).
    cstate, p2a = batched.coordinator_sequence(cstate, all_values, all_active)

    # (4) local acceptor vote.
    astate, votes = batched.acceptor_phase2(astate, p2a, aid=my_idx)

    # (5)+(6) quorum by psum of agree bits.  A dead acceptor contributes 0
    # and must also not mutate its durable state (it is "off the fabric").
    voted = (votes.msgtype == MSG_P2B) & alive                    # [B]
    count = jax.lax.psum(voted.astype(jnp.int32), axis)           # [B]
    decided = count >= quorum

    # Decided value: under a single live coordinator every accept in this
    # round carries the P2A value itself.
    return astate, cstate, decided, p2a.inst, p2a.value


def make_fabric_consensus(
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "data",
    quorum: int | None = None,
    n_instances: int = 4096,
    value_words: int = 16,
) -> tuple[
    Callable[[], tuple[AcceptorState, CoordinatorState]],
    Callable[..., Any],
]:
    """Build a jitted in-fabric consensus step over ``mesh[axis]``.

    Returns ``(init_fn, step_fn)``:
      * ``init_fn()`` -> (astate_sharded, cstate)
      * ``step_fn(astate, cstate, values, active, alive)`` ->
        (astate', cstate', decided[B], inst[B], value[B,V])
    Acceptor state carries a leading per-acceptor shard dim; proposals are
    sharded over the same axis.
    """
    n_acc = mesh.shape[axis]
    q = quorum if quorum is not None else n_acc // 2 + 1

    shard = jax.sharding.NamedSharding(mesh, P(axis))
    replicated = jax.sharding.NamedSharding(mesh, P())

    def init_fn() -> tuple[AcceptorState, CoordinatorState]:
        astate = AcceptorState(
            rnd=jnp.zeros((n_acc, n_instances), jnp.int32),
            vrnd=jnp.full((n_acc, n_instances), NO_ROUND, jnp.int32),
            value=jnp.zeros((n_acc, n_instances, value_words), jnp.int32),
        )
        astate = jax.device_put(astate, shard)
        cstate = jax.device_put(CoordinatorState.init(), replicated)
        return astate, cstate

    def local_round(
        astate: AcceptorState,
        cstate: CoordinatorState,
        values: jax.Array,
        active: jax.Array,
        alive: jax.Array,
    ) -> tuple[
        AcceptorState, CoordinatorState, jax.Array, jax.Array, jax.Array
    ]:
        # strip the per-shard leading dim inside shard_map
        a = AcceptorState(astate.rnd[0], astate.vrnd[0], astate.value[0])
        a, cstate, decided, inst, value = consensus_round(
            a, cstate, values, active, alive[0], axis=axis, quorum=q
        )
        a = AcceptorState(a.rnd[None], a.vrnd[None], a.value[None])
        return a, cstate, decided, inst, value

    fn = _shard_map(
        local_round,
        mesh=mesh,
        # pytree containers double as spec pytrees here (the shard_map
        # convention), hence the arg-type ignores on Array-typed fields
        in_specs=(
            AcceptorState(P(axis), P(axis), P(axis)),  # type: ignore[arg-type]
            CoordinatorState(P(), P()),  # type: ignore[arg-type]
            P(axis, None),
            P(axis),
            P(axis),
        ),
        out_specs=(
            AcceptorState(P(axis), P(axis), P(axis)),  # type: ignore[arg-type]
            CoordinatorState(P(), P()),  # type: ignore[arg-type]
            P(),   # decided: replicated (every shard learns identically)
            P(),
            P(),
        ),
    )
    return init_fn, jax.jit(fn)


# ---------------------------------------------------------------------------
# Groups-sharded multi-group wire path: G groups partitioned over a mesh axis
# ---------------------------------------------------------------------------
def make_sharded_multigroup_round(
    mesh: jax.sharding.Mesh,
    *,
    n_groups: int,
    quorum: int,
    axis: str = "groups",
    use_kernels: bool = False,
    group_block: int = 1,
    window_blocks: int | None = None,
) -> Callable[..., Any]:
    """Build the groups-sharded fused dispatch (DESIGN.md §6): ONE compiled
    program advances all G groups one Phase-2 round, with the ``(G, A, N)``
    acceptor slabs and ``(G, N)`` learner slabs partitioned over
    ``mesh[axis]`` so G scales with device count instead of one chip's
    VMEM/HBM.

    Per-group scalar metadata — the ``(G,)`` watermark/round vectors, the
    ``(G, A)`` alive mask and the ``(G,)`` membership ``enabled`` mask —
    enters *replicated*: it is tiny, host-mutated control state, and each
    shard selects its own window by group offset
    (``kernels.wirepath.shard_slab_round``).  The ring slabs stay
    shard-local and nothing crosses the mesh axis during a round, because
    groups share no state; the quorum reduction runs down the acceptor axis
    *inside* each shard's slab.  Disabled (frozen/vacant/idle) groups ride
    along inert — see the enabled-mask path in ``kernels.wirepath``
    (DESIGN.md §7): membership events therefore never move slab state.

    Returns ``sharded_multigroup_round(next_inst[G], crnd[G], enabled[G],
    alive[G, A], stack, lstate, values[G, B, V], active[G, B]) -> (stack',
    lstate', fresh[G, B], inst[G, B], win[G, B], value[G, B, V])`` with the
    state arguments donated (device-resident in place across rounds).

    Under the cohort dispatch planner (DESIGN.md §8) the same step serves
    every tier of a round plan: ``B`` is the tier's right-sized burst (the
    step retraces per distinct pow2 burst), the ``enabled`` mask is the
    tier's membership, ``group_block`` the fold width
    (``core.plan.full_fold`` against the per-shard slab), and
    ``window_blocks`` the kernel's ring blocks per group
    (``core.plan.sharded_window_blocks``; ``None`` covers any offset); the
    programs a dataplane may build are ``core.plan.sharded_programs``.  The
    group axis is *not* compacted here — shard_map
    needs uniform per-shard shapes, and a cohort may concentrate on one
    shard — so non-member slabs ride each tier inert; the unsharded
    dataplane additionally compacts via
    ``kernels.wirepath.cohort_wirepath_round``.
    """
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    n_sh = mesh.shape[axis]
    if n_groups % n_sh:
        raise ValueError(
            f"n_groups={n_groups} must be divisible by the {axis!r} mesh "
            f"axis size {n_sh}"
        )
    gl = n_groups // n_sh
    if group_block > 1 and gl % group_block:
        raise ValueError(
            f"group_block={group_block} must divide the per-shard slab {gl}"
        )
    offsets = jnp.arange(n_sh, dtype=jnp.int32) * gl
    q = quorum

    def local(
        ni: jax.Array,
        cr: jax.Array,
        en: jax.Array,
        alive: jax.Array,
        lim: jax.Array,
        off: jax.Array,
        stack: AcceptorState,
        lstate: batched.LearnerState,
        values: jax.Array,
        active: jax.Array,
    ) -> tuple[
        AcceptorState, batched.LearnerState, jax.Array, jax.Array,
        jax.Array, jax.Array,
    ]:
        # off is this shard's (1,)-slice of the offset vector: the global id
        # of the slab's first group.  Scalar vectors stay global (including
        # the replicated reclaim-limit vector, DESIGN.md §9); slabs are local.
        ni_l = jax.lax.dynamic_slice(ni, (off[0],), (gl,))
        if use_kernels:
            from repro.kernels import ops as kops
            from repro.kernels import wirepath as kwp

            del active  # sequenced fillers vote like P2As (DESIGN.md §3)
            outs = kwp.shard_slab_round(
                off[0], ni, cr, jnp.int32(q), alive,
                stack.rnd, stack.vrnd, stack.value,
                lstate.delivered, lstate.inst, lstate.value, values, en, lim,
                group_block=group_block, window_blocks=window_blocks,
                interpret=kops.INTERPRET,
            )
            stack = AcceptorState(*outs[:3])
            lstate = batched.LearnerState(*outs[3:6])
            fresh, win, value = outs[6] != 0, outs[7], outs[8]
        else:
            cr_l = jax.lax.dynamic_slice(cr, (off[0],), (gl,))
            en_l = jax.lax.dynamic_slice(en, (off[0],), (gl,))
            cr_l = jnp.where(en_l != 0, cr_l, NO_ROUND)
            al_l = jax.lax.dynamic_slice(
                alive, (off[0], 0), (gl, alive.shape[1])
            )
            lim_l = jax.lax.dynamic_slice(lim, (off[0],), (gl,))
            cs = CoordinatorState(next_inst=ni_l, crnd=cr_l)
            _c, stack, lstate, fresh, _i, win, value = (
                batched.multigroup_fused_round(
                    cs, stack, lstate, values, active, al_l != 0, q,
                    reclaim_limit=lim_l,
                )
            )
        b = values.shape[1]
        inst = ni_l[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
        return stack, lstate, fresh, inst, win, value

    sheet = P(axis)
    if n_sh == 1:
        # single-shard fast path, same argument as make_packed_sharded_round
        # below: one shard's local block IS the global array for every spec,
        # so the shard body runs bit-identically under plain jit and skips
        # shard_map's fixed per-call resharding of the slab state
        fn = local
    else:
        fn = _shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(),                               # next_inst (replicated)
                P(),                               # crnd (replicated)
                P(),                               # enabled (replicated)
                P(),                               # alive (replicated)
                P(),                               # reclaim limit (replicated)
                sheet,                             # offsets
                AcceptorState(sheet, sheet, sheet),  # type: ignore[arg-type]
                batched.LearnerState(sheet, sheet, sheet),  # type: ignore[arg-type]
                sheet,                             # values
                sheet,                             # active
            ),
            out_specs=(
                AcceptorState(sheet, sheet, sheet),  # type: ignore[arg-type]
                batched.LearnerState(sheet, sheet, sheet),  # type: ignore[arg-type]
                sheet,                             # fresh
                sheet,                             # inst
                sheet,                             # win
                sheet,                             # value
            ),
        )

    # the name of the XLA module on the device's trace
    def sharded_multigroup_round(
        next_inst: Any,
        crnd: Any,
        enabled: Any,
        alive: Any,
        stack: AcceptorState,
        lstate: batched.LearnerState,
        values: jax.Array,
        active: jax.Array,
        reclaim_limit: Any | None = None,
    ) -> Any:
        if reclaim_limit is None:
            # full permit: int32.max is unreachable, every lane passes the
            # reclamation gate (legacy overwrite-on-wrap mode)
            lim = jnp.full((n_groups,), jnp.iinfo(jnp.int32).max, jnp.int32)
        else:
            lim = jnp.asarray(reclaim_limit, jnp.int32).reshape((n_groups,))
        return fn(
            jnp.asarray(next_inst, jnp.int32).reshape((n_groups,)),
            jnp.asarray(crnd, jnp.int32).reshape((n_groups,)),
            jnp.asarray(enabled, jnp.int32).reshape((n_groups,)),
            jnp.asarray(alive, jnp.int32),
            lim,
            offsets,
            stack,
            lstate,
            values,
            active,
        )

    return jax.jit(sharded_multigroup_round, donate_argnums=(4, 5))


def make_packed_sharded_round(
    mesh: jax.sharding.Mesh,
    *,
    quorum: int,
    axis: str = "groups",
    use_kernels: bool = False,
    block_b: int | None = None,
    window_blocks: int | None = None,
) -> Callable[..., Any]:
    """Build the *packed* groups-sharded cohort dispatch (DESIGN.md §13):
    each shard advances only its resident, enabled cohort lanes — packed
    into a uniform ``(n_sh, C)`` lane table — instead of walking its full
    ``Gl``-row slab with non-members held inert.

    Where ``make_sharded_multigroup_round`` satisfies shard_map's shape
    uniformity by running full-width slabs per tier (cold cohorts pay
    full-width slab cost), here uniformity comes from the GShard MoE
    input-packing idiom: ``C`` lanes per shard (the cohort's max per-shard
    residency), each lane routed to its slab row by a ``segids`` table
    riding scalar prefetch, with pad lanes (``enabled == 0``) inert.  All
    control tables are per-LANE, packed by the caller in lane order:

        packed_sharded_round(segids[S, C], next_inst[S, C], crnd[S, C],
             enabled[S, C], alive[S, C, A], stack, lstate,
             values[S, C, B, V], reclaim_limit[S, C] | None)
          -> (stack', lstate', fresh[S*C, B], inst[S*C, B], win[S*C, B],
              value[S*C, B, V])

    with shard ``s``'s lane ``j`` at packed row ``s*C + j`` of the outputs,
    state donated in place, and the slab state updated bit-identically to
    the full-width dispatch (pads and absent rows untouched).  ``C`` is a
    trace-time shape: the step retraces per distinct (C, B), both drawn
    from ``core.plan.sharded_programs``.  ``window_blocks`` is
    the kernel's ring blocks per lane (``None`` covers any offset).
    """
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    q = quorum

    def local(
        ni: jax.Array,
        cr: jax.Array,
        en: jax.Array,
        alive: jax.Array,
        lim: jax.Array,
        seg: jax.Array,
        stack: AcceptorState,
        lstate: batched.LearnerState,
        values: jax.Array,
    ) -> tuple[
        AcceptorState, batched.LearnerState, jax.Array, jax.Array,
        jax.Array, jax.Array,
    ]:
        # every control table is a per-lane (1, C[, A]) sheet of this
        # shard's packed lanes; slabs are local (Gl rows, slot-indexed)
        if use_kernels:
            from repro.kernels import ops as kops
            from repro.kernels import wirepath as kwp

            # block_b is a kernel-path grid knob only (the oracle has no
            # blocks); None keeps the kernel's own default
            kw: dict[str, int] = {} if block_b is None else {"block_b": block_b}
            outs = kwp.packed_shard_round(
                seg[0], ni[0], cr[0], jnp.int32(q), alive[0],
                stack.rnd, stack.vrnd, stack.value,
                lstate.delivered, lstate.inst, lstate.value, values[0],
                en[0], lim[0], window_blocks=window_blocks,
                interpret=kops.INTERPRET, **kw,
            )
            stack = AcceptorState(*outs[:3])
            lstate = batched.LearnerState(*outs[3:6])
            fresh, win, value = outs[6] != 0, outs[7], outs[8]
        else:
            stack, lstate, fresh, win, value = (
                batched.packed_multigroup_round(
                    stack, lstate, seg[0], ni[0], cr[0], alive[0], q,
                    values[0], en[0], reclaim_limit=lim[0],
                )
            )
        b = values.shape[2]
        inst = ni[0][:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
        return stack, lstate, fresh, inst, win, value

    sheet = P(axis)
    if mesh.shape[axis] == 1:
        # a single-shard mesh partitions nothing: every global table equals
        # its one local block, so the shard body IS the global computation.
        # Dispatching through shard_map anyway would only buy its fixed
        # per-call resharding of the slab state — a pure copy tax on the
        # interpret backend — for zero layout change.  Multi-shard meshes
        # (the multidevice suite) take the shard_map path below and are
        # bit-identical by construction: same `local`, same operands.
        fn = local
    else:
        fn = _shard_map(
            local,
            mesh=mesh,
            in_specs=(
                sheet,                             # next_inst (per-lane)
                sheet,                             # crnd (per-lane)
                sheet,                             # enabled (per-lane)
                sheet,                             # alive (per-lane)
                sheet,                             # reclaim limit (per-lane)
                sheet,                             # segids (per-lane)
                AcceptorState(sheet, sheet, sheet),  # type: ignore[arg-type]
                batched.LearnerState(sheet, sheet, sheet),  # type: ignore[arg-type]
                sheet,                             # values (per-lane)
            ),
            out_specs=(
                AcceptorState(sheet, sheet, sheet),  # type: ignore[arg-type]
                batched.LearnerState(sheet, sheet, sheet),  # type: ignore[arg-type]
                sheet,                             # fresh
                sheet,                             # inst
                sheet,                             # win
                sheet,                             # value
            ),
        )

    # the name of the XLA module on the device's trace
    def packed_sharded_round(
        segids: Any,
        next_inst: Any,
        crnd: Any,
        enabled: Any,
        alive: Any,
        stack: AcceptorState,
        lstate: batched.LearnerState,
        values: jax.Array,
        reclaim_limit: Any | None = None,
    ) -> Any:
        s, c = values.shape[0], values.shape[1]
        if reclaim_limit is None:
            lim = jnp.full((s, c), jnp.iinfo(jnp.int32).max, jnp.int32)
        else:
            lim = jnp.asarray(reclaim_limit, jnp.int32).reshape((s, c))
        return fn(
            jnp.asarray(next_inst, jnp.int32).reshape((s, c)),
            jnp.asarray(crnd, jnp.int32).reshape((s, c)),
            jnp.asarray(enabled, jnp.int32).reshape((s, c)),
            jnp.asarray(alive, jnp.int32),
            lim,
            jnp.asarray(segids, jnp.int32).reshape((s, c)),
            stack,
            lstate,
            values,
        )

    return jax.jit(packed_sharded_round, donate_argnums=(5, 6))


# ---------------------------------------------------------------------------
# Quorum step-commit for distributed training (straggler mitigation)
# ---------------------------------------------------------------------------
def quorum_commit_digest(
    digest: jax.Array,       # int32[] or int32[k]  this replica-group's digest
    healthy: jax.Array,      # bool []              this group voted in time
    *,
    axis: str,
    quorum: int,
) -> tuple[jax.Array, jax.Array]:
    """Decide a training step commit by digest agreement (inside shard_map).

    Each data-parallel replica group votes with the digest of its gradient
    contribution; the step commits iff >= quorum healthy groups hold the
    identical digest.  A straggling / dead group (healthy=False) cannot block
    the step — the paper's f-of-2f+1 resilience doubles as straggler
    mitigation.

    Returns (commit: bool[], winning_count: int32[]).
    """
    d = jnp.atleast_1d(digest)
    all_d = jax.lax.all_gather(d, axis)                      # [G, k]
    all_h = jax.lax.all_gather(healthy, axis)                # [G]
    eq = jnp.all(all_d[:, None, :] == all_d[None, :, :], -1)  # [G, G]
    eq = eq & all_h[None, :] & all_h[:, None]
    counts = jnp.sum(eq.astype(jnp.int32), axis=1)           # votes per digest
    win = jnp.max(counts)
    return win >= quorum, win
