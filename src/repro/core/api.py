"""The drop-in CAANS application API (paper Fig. 4).

    submit(ctx, value, size)          -> propose a value
    ctx.deliver = cb(value, size, inst)  (registered callback)
    recover(ctx, inst, nop, size)     -> learn a previously decided instance

A ``PaxosContext`` wires software proposers/learners to the "hardware"
coordinator/acceptor dataplane.  The dataplane is the jitted batched engine
(or the Pallas kernels when ``use_kernels=True``) — the same hardware/software
divide as the paper: applications only ever see ``submit``/``deliver``/
``recover``; everything between is the network's problem.

Messages between the host roles travel over the fault-injected ``SimNet``;
retransmission on timeout (counted in ``pump`` rounds) and duplicate
suppression at learners implement the paper's §3.1 failure-handling contract.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import time
from typing import Any
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..analysis.contracts import mirror_guard
from . import batched
from . import plan as plan_mod
from .network import SimNet
from .paxos import Coordinator as SoftCoordinator
from .plan import NO_ROUND, NOP_SENTINEL
from .snapshot import GroupSnapshot, RingReclamationMixin, SnapshotStore
from .types import (
    MSG_NOP,
    MSG_P1A,
    MSG_P2A,
    MSG_P2B,
    AcceptorState,
    CoordinatorState,
    MsgBatch,
    PaxosConfig,
)


def default_use_kernels() -> bool:
    """The engine a context runs when not told: the Pallas kernels on a TPU
    backend, the jnp oracle elsewhere (where the kernels only interpret)."""
    return jax.default_backend() == "tpu"


def _kernel_blocks(dp, bases, b: int) -> int | None:
    """Ring blocks per group a kernel dispatch of ``b``-slot windows at
    ``bases`` visits, or ``None`` when the dispatch runs the jnp engine
    (a jnp dataplane, or a window the kernels cannot run)."""
    if not dp.use_kernels:
        return None
    return plan_mod.window_blocks(dp.cfg.n_instances, list(bases), b)


class _DispatchCounter:
    """``dispatch_count`` counts every device program launch (the KV tier
    pins its consensus-free read claim on it staying flat);
    ``jnp_dispatch_count`` counts the launches that ran the jnp engine — on
    a kernel dataplane each one is a fallback (recovery traffic, or a window
    the kernels cannot run), never a silent one."""

    dispatch_count = 0
    jnp_dispatch_count = 0
    persistent_dispatch_count = 0   # K-round waves run as one launch

    def _count(self, kernel: bool) -> None:
        self.dispatch_count += 1
        if not kernel:
            self.jnp_dispatch_count += 1


@dataclasses.dataclass
class _Pending:
    payload: bytes
    age: int = 0
    group: int = 0
    # host clock at submit: a wire burst's queue wait is measured from here
    t_submit: float = dataclasses.field(default_factory=time.perf_counter)


class _DeferredRound:
    """Handle for a dispatched wave whose host read-back is deferred
    (DESIGN.md §11): the dispatch is in flight (or complete) device-side,
    and ``resolve()`` performs the device->host transfer plus the cohort
    row selection.  The double-buffered pump dispatches wave N+1 before
    resolving wave N, overlapping host planning/packing with device
    execution; host watermark mirrors were already advanced at dispatch
    time, so planning never waits on a resolve."""

    def __init__(self, fresh, value, inst, rows=None, axis=0):
        self._fresh = fresh     # device (or host) array, pre-selection
        self._value = value
        self._inst = inst       # host instance windows, already selected
        self._rows = None if rows is None else list(rows)
        self._axis = axis       # cohort-row axis of fresh/value

    @classmethod
    def resolved(cls, fresh, value, inst):
        """An already-host-side result wrapped for interface uniformity
        (the sharded dataplane reads back eagerly)."""
        return cls(fresh, value, inst, rows=None)

    def resolve(self):
        fresh = np.asarray(self._fresh)
        value = np.asarray(self._value)
        if self._rows is not None:
            fresh = np.take(fresh, self._rows, axis=self._axis)
            value = np.take(value, self._rows, axis=self._axis)
        return fresh, self._inst, value


def _fused_round_packed(
    cstate: CoordinatorState,
    stack: AcceptorState,
    lstate: batched.LearnerState,
    burst: jax.Array,
    alive: jax.Array,
    reclaim_limit: jax.Array | None = None,
    *,
    quorum: int,
    window_blocks: int | None = None,
) -> tuple[CoordinatorState, AcceptorState, batched.LearnerState, jax.Array]:
    """The single-group fused round with one array in and one array out, so
    that a dispatch makes one host->device and one device->host transfer.

    ``window_blocks`` picks the engine as ``_kernel_blocks`` does: the Pallas
    megakernel over that many ring blocks, or the jnp oracle when ``None``.
    The kernel never reads the active mask, so its ``burst`` is the int32[B, V]
    values alone; the oracle's sequencer does, so its burst carries the mask
    as a last column, int32[B, V+1].  ``quorum`` is a constant of the
    deployment, compiled in.  Returns the new state and int32[B, V+2]: each
    lane's fresh flag, instance and V value words.
    """
    if window_blocks is None:
        values, active = burst[:, :-1], burst[:, -1] != 0
        out = batched.fused_round(
            cstate, stack, lstate, values, active, alive, quorum, reclaim_limit
        )
    else:
        from repro.kernels import ops as kops

        out = kops.fused_round(
            cstate,
            stack,
            lstate,
            burst,
            None,
            alive,
            quorum,
            reclaim_limit,
            window_blocks=window_blocks,
        )
    cstate, stack, lstate, fresh, inst, _win, value = out
    packed = jnp.concatenate(
        [fresh.astype(jnp.int32)[:, None], inst[:, None], value], axis=1
    )
    return cstate, stack, lstate, packed


class HardwareDataplane(RingReclamationMixin, _DispatchCounter):
    """The coordinator + acceptor array + learner dedup memory, executing as
    single-dispatch device programs.

    Two execution paths (DESIGN.md §3):

      * ``pipeline()`` — the fused wire path: the whole Phase-2 round
        (sequence -> all-A vote -> quorum -> ring dedup) as ONE program; the
        Pallas megakernel ``kernels.wirepath.wirepath_round`` when
        ``use_kernels``, else the jnp oracle ``batched.fused_round``.  All
        protocol state stays resident in device memory across pump rounds.
      * ``sequence()``/``vote()``/``prepare()`` — the staged path, used when
        votes must surface as messages (per-learner fan-out, recovery,
        software-coordinator failover).  Still one dispatch for the whole
        acceptor array: the historical per-acceptor Python loop (and its
        per-vote ``.at[aid].set`` full-stack rewrites) is gone.

    Liveness is a device-resident runtime mask (``alive_mask``), so
    ``kill_acceptor``/``revive_acceptor`` never trigger recompilation.
    """

    def __init__(self, cfg: PaxosConfig, use_kernels: bool = False):
        self.cfg = cfg
        self.cstate = CoordinatorState.init()
        # acceptor register files, permanently stacked (A, ...) — the paper's
        # per-device BRAM, one shard per acceptor
        one = AcceptorState.init(cfg.n_instances, cfg.value_words)
        self.stack: AcceptorState = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_acceptors,) + x.shape).copy(), one
        )
        self.lstate = batched.LearnerState.init(cfg.n_instances, cfg.value_words)
        self.alive = [True] * cfg.n_acceptors       # host mirror (introspection)
        self.alive_mask = jnp.ones((cfg.n_acceptors,), jnp.bool_)
        self.use_kernels = use_kernels
        # host mirror of the sequencer watermark — sizes the kernel's ring
        # window without a device sync
        self._next_inst_host = 0
        self._seq_base: int | None = None        # provenance hint for vote()
        # the reclamation limit (watermark + N) on the device, replaced only
        # when the watermark moves; None while reclamation is disabled
        self._reclaim_limit_dev: jax.Array | None = None
        if use_kernels:
            from repro.kernels import ops as kops

            self._seq = kops.coordinator_sequence
            self._vote_all_k = jax.jit(
                kops.acceptor_phase2_all, donate_argnums=(0,)
            )
        else:
            self._seq = jax.jit(batched.coordinator_sequence)
        self._fused = jax.jit(
            _fused_round_packed,
            donate_argnums=(1, 2),
            static_argnames=("quorum", "window_blocks"),
        )
        self._vote_all = jax.jit(batched.acceptor_phase2_all, donate_argnums=(0,))
        self._prep_all = jax.jit(batched.acceptor_phase1_all, donate_argnums=(0,))

    # -- ring reclamation: RingReclamationMixin at G == 1 (DESIGN.md §9) -----
    def _seq_marks(self) -> list[int]:
        return [self._next_inst_host]

    @property
    def reclaimed_host(self) -> int | None:
        """Scalar view of the single group's reclamation watermark (None
        while reclamation is disabled) — the historical public surface."""
        marks = self._reclaim_marks
        return None if marks is None else marks[0]

    @mirror_guard
    def enable_reclamation(self) -> None:
        super().enable_reclamation()
        self._hold_reclaim_limit()

    @mirror_guard
    def set_reclaimed(self, upto: int) -> None:
        """Advance the reclamation watermark: instances below ``upto`` have
        been drained to a snapshot and their ring slots may be re-used."""
        self._reclaim_set(0, upto)
        self._hold_reclaim_limit()

    @mirror_guard
    def _hold_reclaim_limit(self) -> None:
        """Upload the reclamation limit once per watermark move, so the
        dispatches between two snapshots upload nothing for it."""
        self._reclaim_limit_dev = jax.device_put(self._reclaim_limits_np()[0])

    def _guard_capacity(self, base: int, b: int) -> None:
        self._reclaim_guard(0, base, b)

    # -- fused fast path: whole Phase-2 round in ONE device program ----------
    @mirror_guard
    def pipeline(self, values: np.ndarray, active: np.ndarray):
        """One dispatch: sequence + all acceptor votes + quorum + dedup.

        This is the CAANS wire path — consensus logic fused end-to-end below
        the host boundary (DESIGN.md §3).  Returns host ``(fresh, inst,
        value)`` where ``fresh`` masks non-duplicate deliveries.  The burst
        is the one upload and the packed result the one read-back; every
        other operand already lives on the device.
        """
        with obs.span("repro.hw.launch") as sp:
            b = values.shape[0]
            self._guard_capacity(self._next_inst_host, b)
            nblk = _kernel_blocks(self, [self._next_inst_host], b)
            if obs.enabled():
                sp.set_metadata(blocks=nblk or 0)
            burst = np.asarray(values, np.int32)
            if nblk is None:
                # the jnp engine's sequencer reads the active mask
                burst = np.concatenate(
                    [burst, np.asarray(active, np.int32)[:, None]], axis=1
                )
            self._count(nblk is not None)
            self.cstate, self.stack, self.lstate, out = self._fused(
                self.cstate,
                self.stack,
                self.lstate,
                jax.device_put(burst),
                self.alive_mask,
                self._reclaim_limit_dev,
                quorum=self.cfg.quorum,
                window_blocks=nblk,
            )
            self._next_inst_host += b
        with obs.span("repro.hw.readback"):
            out = jax.device_get(out)
            return out[:, 0] != 0, out[:, 1], out[:, 2:]

    def kill_acceptor(self, aid: int) -> None:
        self.alive[aid] = False
        self.alive_mask = self.alive_mask.at[aid].set(False)

    def revive_acceptor(self, aid: int) -> None:
        self.alive[aid] = True
        self.alive_mask = self.alive_mask.at[aid].set(True)

    def wipe_acceptor(self, aid: int) -> None:
        """Model a crash WITH state loss: zero the acceptor's register file
        (its BRAM), unlike ``kill_acceptor`` which freezes it intact.  The
        revival path rebuilds from snapshot + live ring suffix
        (``core.failover.restore_acceptor``, DESIGN.md §9)."""
        fresh = AcceptorState.init(self.cfg.n_instances, self.cfg.value_words)
        self.stack = jax.tree_util.tree_map(
            lambda s, f: s.at[aid].set(f), self.stack, fresh
        )

    # -- staged path (votes surface as messages) -----------------------------
    @mirror_guard
    def sequence(self, values: np.ndarray, active: np.ndarray) -> MsgBatch:
        self._guard_capacity(self._next_inst_host, values.shape[0])
        self._seq_base = self._next_inst_host
        self._count(self.use_kernels)
        self.cstate, p2a = self._seq(
            self.cstate, jnp.asarray(values), jnp.asarray(active)
        )
        self._next_inst_host += values.shape[0]
        return p2a

    def vote(self, p2a: MsgBatch) -> list[MsgBatch | None]:
        """Phase-2 vote of the whole acceptor array, one dispatch.

        Batches produced by ``sequence()`` on a ring-block-aligned window go
        through the Pallas vote kernel when ``use_kernels``; anything else
        (recovery singletons, software-coordinator batches at arbitrary
        watermarks) takes the general jnp scatter path, counted in
        ``jnp_dispatch_count``.  Dead acceptors come back as ``None`` —
        their votes are never sent.
        """
        base, self._seq_base = self._seq_base, None
        b = p2a.batch
        use_k = (
            self.use_kernels
            and base is not None
            and plan_mod.blocks_aligned(self.cfg.n_instances, [base], b)
        )
        fn = self._vote_all_k if use_k else self._vote_all
        self._count(use_k)
        self.stack, votes = fn(self.stack, p2a, self.alive_mask)
        return self._split(votes)

    def prepare(self, p1a: MsgBatch) -> list[MsgBatch | None]:
        self._count(False)
        self.stack, outs = self._prep_all(self.stack, p1a, self.alive_mask)
        return self._split(outs)

    def _split(self, stacked: MsgBatch) -> list[MsgBatch | None]:
        """Stacked [A, ...] message batches -> per-acceptor list, None when
        dead (a crashed switch emits nothing)."""
        return [
            jax.tree_util.tree_map(lambda x, aid=aid: x[aid], stacked)
            if self.alive[aid]
            else None
            for aid in range(self.cfg.n_acceptors)
        ]


class _GroupView:
    """Single-group staged-path adapter over one group's slice of the stack.

    Exposes the ``prepare``/``vote``/``cfg`` surface that ``core.failover``
    and the recovery path expect from a ``HardwareDataplane``, but reads and
    writes only group ``gid``'s rows of the multi-group ``(G, A, N)`` state —
    the other groups' registers are never touched.  Not a fast path: recovery
    and failover traffic only.
    """

    def __init__(self, mg: "MultiGroupDataplane", gid: int):
        self.mg = mg
        self.gid = gid

    @property
    def cfg(self) -> PaxosConfig:
        return self.mg.cfg

    def vote(self, p2a: MsgBatch) -> list[MsgBatch | None]:
        mg, gid = self.mg, self.gid
        row = mg._slab_row(gid)
        mg._count(False)
        st = jax.tree_util.tree_map(lambda x: x[row], mg.stack)
        st, votes = mg._vote_all(st, p2a, mg.alive_mask[gid])
        mg.stack = jax.tree_util.tree_map(
            lambda s, n: s.at[row].set(n), mg.stack, st
        )
        return self._split(votes)

    def prepare(self, p1a: MsgBatch) -> list[MsgBatch | None]:
        mg, gid = self.mg, self.gid
        row = mg._slab_row(gid)
        mg._count(False)
        st = jax.tree_util.tree_map(lambda x: x[row], mg.stack)
        st, outs = mg._prep_all(st, p1a, mg.alive_mask[gid])
        mg.stack = jax.tree_util.tree_map(
            lambda s, n: s.at[row].set(n), mg.stack, st
        )
        return self._split(outs)

    def _split(self, stacked: MsgBatch) -> list[MsgBatch | None]:
        gid = jnp.int32(self.gid)
        return [
            jax.tree_util.tree_map(lambda x, aid=aid: x[aid], stacked).replace(
                gid=gid
            )
            if self.mg.alive[self.gid][aid]
            else None
            for aid in range(self.cfg.n_acceptors)
        ]


class MultiGroupDataplane(RingReclamationMixin, _DispatchCounter):
    """G device-resident Paxos groups sharing one fused dispatch per round —
    consensus as a service, the NetChain-style generalization of
    ``HardwareDataplane`` (DESIGN.md §5).

    State is the single-group layout grown a leading group axis: ``(G,)``
    coordinator watermarks/rounds, ``(G, A, N)`` acceptor rings, ``(G, N)``
    learner rings, a ``(G, A)`` runtime liveness mask.  ``pipeline`` advances
    *every* group one Phase-2 round in one device program — the Pallas
    multi-group megakernel when ``use_kernels`` (folding groups into each
    grid step when the host watermark mirrors are in lockstep), else the
    vmapped jnp oracle.

    Per-group failover support: ``freeze_group`` parks a group's coordinator
    round at ``NO_ROUND`` so the shared dispatch can keep running — a frozen
    group's slots are all rejected, deciding (and perturbing) nothing — and
    ``restore_group`` realigns the group's watermark/round after a software
    coordinator hands back control.  ``group_view`` exposes one group's
    staged surface for recovery and takeover.

    Dynamic membership (DESIGN.md §7): ``cfg.n_groups`` is a *capacity* —
    the ``(G_cap, A, N)`` slabs stay allocated at it, and a host-side
    free-list over the group axis lets tenants come and go at runtime.
    ``retire_group`` is host-scalar-only (drain + park at NO_ROUND + free),
    ``create_group`` claims the lowest free slot and zeroes only that slot's
    rings; neither touches any other group's slab state.
    """

    def __init__(
        self, cfg: PaxosConfig, use_kernels: bool = False, slab_sharding=None
    ):
        if cfg.n_groups < 1:
            raise ValueError(f"n_groups must be >= 1, got {cfg.n_groups}")
        self.cfg = cfg
        g, a = cfg.n_groups, cfg.n_acceptors
        self.cstate, self.stack, self.lstate = batched.init_multigroup_state(
            g, a, cfg.n_instances, cfg.value_words, sharding=slab_sharding
        )
        self.alive = [[True] * a for _ in range(g)]   # host mirror
        self.alive_mask = jnp.ones((g, a), jnp.bool_)
        # dynamic membership: every capacity slot starts live; the free-list
        # (sorted, lowest-first: deterministic allocation) holds vacant slots
        self.live_host: list[bool] = [True] * g
        self._free: list[int] = []
        self.use_kernels = use_kernels
        # per-group host mirrors of the sequencer watermark and round — the
        # kernel path's window/lockstep decisions cost no device sync
        self.next_inst_host: list[int] = [0] * g
        self.crnd_host: list[int] = [0] * g
        self.last_gb: int | None = None   # fold width of the last dispatch
        if use_kernels:
            from repro.kernels import ops as kops

            self._fused_k = jax.jit(
                kops.multigroup_fused_round,
                donate_argnums=(1, 2),
                static_argnames=("group_block", "window_blocks"),
            )
            self._cohort_k = jax.jit(
                kops.cohort_fused_round,
                donate_argnums=(0, 1),
                static_argnames=("group_block", "window_blocks"),
            )
            self._persist_k = jax.jit(
                kops.persistent_cohort_rounds,
                donate_argnums=(0, 1),
                static_argnames=("group_block", "block_b"),
            )
        self._fused = jax.jit(
            batched.multigroup_fused_round, donate_argnums=(1, 2)
        )
        self._persist_j = jax.jit(
            batched.persistent_multigroup_rounds, donate_argnums=(1, 2)
        )
        self._vote_all = jax.jit(batched.acceptor_phase2_all)
        self._prep_all = jax.jit(batched.acceptor_phase1_all)

    # -- ring reclamation: RingReclamationMixin per group (DESIGN.md §9) -----
    def _seq_marks(self) -> list[int]:
        return self.next_inst_host

    @property
    def reclaimed_host(self) -> list[int] | None:
        """Per-group watermark vector (None while disabled).  The list IS
        the mixin's live state: membership paths (``create_group``/
        ``adopt_group``) reset their slot in place."""
        return self._reclaim_marks

    def set_reclaimed(self, gid: int, upto: int) -> None:
        """Advance group ``gid``'s reclamation watermark after a snapshot
        drain of instances below ``upto``."""
        self._check_gid(gid)
        self._reclaim_set(gid, upto)

    def _reclaim_limits(self) -> jax.Array | None:
        """Device form of the mixin's first-refused-instance vector."""
        lim = self._reclaim_limits_np()
        return None if lim is None else jnp.asarray(lim)

    def _guard_capacity(self, gids, b: int) -> None:
        for gid in gids:
            self._reclaim_guard(gid, self.next_inst_host[gid], b)

    # -- shared pre-dispatch plan (the parity contract between this class
    # and its sharded subclass: both MUST resolve a round identically) ------
    def _fold_width(self) -> int:
        """Widest fold of groups into one grid step (the whole service
        here, one shard's slab in the sharded subclass), capped by what one
        step may hold in VMEM (``core.plan.fold_cap``)."""
        cfg = self.cfg
        return plan_mod.fold_cap(
            cfg.n_groups, cfg.n_instances, cfg.n_acceptors, cfg.value_words
        )

    def _plan_round(self, b: int, enabled: list[bool] | None):
        """Resolve the enabled mask against membership and frozen rounds,
        size the kernel's ring window from the host watermark mirrors, and
        pick the fold width (``core.plan.fold_width_full`` — the widest
        divisor of the fold cap whose aligned blocks are internally
        lockstep, not the historical all-or-nothing fold).  Returns
        ``(enabled, window_blocks, group_block)``; ``window_blocks`` is
        ``None`` when the round runs the jnp engine.

        Only *enabled* groups constrain the plan: a disabled group — frozen,
        vacant (retired), or idle this round — rides the dispatch inert at
        whatever watermark it has (the kernel's enabled-mask path substitutes
        a folded block's ring offset for it), so divergent disabled
        watermarks neither widen the window nor forfeit the lockstep fold."""
        if enabled is None:
            enabled = [
                lv and c != NO_ROUND
                for lv, c in zip(self.live_host, self.crnd_host, strict=True)
            ]
        else:
            enabled = [
                bool(e) and lv and c != NO_ROUND
                for e, lv, c in zip(enabled, self.live_host, self.crnd_host, strict=True)
            ]
        en_gids = [i for i, e in enumerate(enabled) if e]
        nblk = _kernel_blocks(self, (self.next_inst_host[g] for g in en_gids), b)
        gb = plan_mod.fold_width_full(
            en_gids, self.next_inst_host, self._fold_width()
        )
        return enabled, nblk, gb

    def _empty_round(self, g: int, b: int):
        """The all-disabled result: nothing would decide, skip dispatch."""
        return (
            np.zeros((g, b), np.int32),
            np.zeros((g, b), np.int32),
            np.zeros((g, b, self.cfg.value_words), np.int32),
        )

    # -- fused fast path: ALL groups advance one round in ONE dispatch -------
    @mirror_guard
    def pipeline(
        self,
        values: np.ndarray,
        active: np.ndarray,
        enabled: list[bool] | None = None,
    ):
        """One dispatch for all G groups: sequence + votes + quorum + dedup.

        ``values`` is ``(G, B, V)``, ``active`` ``(G, B)``.  ``enabled``
        masks which groups actually advance this round (default: those whose
        round is not frozen).  A disabled group rides along *inert*: its
        round is presented to the dispatch as NO_ROUND so its acceptors
        reject every slot, and its watermark does not move — so an idle
        group burns no ring instances and its state stays bit-identical to
        an independent deployment that simply wasn't pumped.  Returns host
        ``(fresh, inst, value)`` with a leading group axis.
        """
        g, b = values.shape[0], values.shape[1]
        enabled, nblk, gb = self._plan_round(b, enabled)
        if not any(enabled):
            return self._empty_round(g, b)
        self._guard_capacity(
            [gid for gid in range(g) if enabled[gid]], b
        )
        lim = self._reclaim_limits()
        en = jnp.asarray(enabled)
        if nblk is not None:
            # the kernel takes the membership mask itself (enabled-mask
            # path): it forces disabled rounds to NO_ROUND and substitutes
            # folded-block watermarks for vacant/frozen members
            fn = functools.partial(
                self._fused_k,
                group_block=gb,
                window_blocks=nblk,
                enabled=en.astype(jnp.int32),
                reclaim_limit=lim,
            )
        elif lim is not None:
            fn = functools.partial(self._fused, reclaim_limit=lim)
        else:
            fn = self._fused
        cs = self.cstate
        eff = CoordinatorState(
            next_inst=cs.next_inst, crnd=jnp.where(en, cs.crnd, NO_ROUND)
        )
        self._count(nblk is not None)
        new_c, self.stack, self.lstate, fresh, inst, _win, value = fn(
            eff,
            self.stack,
            self.lstate,
            jnp.asarray(values),
            jnp.asarray(active),
            self.alive_mask,
            self.cfg.quorum,
        )
        # disabled groups keep their watermark and their true round
        self.cstate = CoordinatorState(
            next_inst=jnp.where(en, new_c.next_inst, cs.next_inst),
            crnd=cs.crnd,
        )
        for gid in range(g):
            if enabled[gid]:
                self.next_inst_host[gid] += b
        self.last_gb = gb          # the plan's fold width, engine-agnostic
        return np.asarray(fresh), np.asarray(inst), np.asarray(value)

    # -- cohort dispatch: one tier of a RoundPlan (DESIGN.md §8) -------------
    def _cohort_prologue(self, gids, values: np.ndarray):
        """Shared pre-dispatch resolution for a cohort tier: membership
        mask, the kernel's ring window (``None`` = jnp engine), and the
        per-member instance windows — identical for the unsharded and
        sharded executions, which is half the parity contract."""
        gids = list(gids)
        be = values.shape[1]
        assert values.shape[0] == len(gids), (values.shape, len(gids))
        marks = self.next_inst_host
        member = np.zeros((self.cfg.n_groups,), np.int32)
        member[gids] = 1
        nblk = _kernel_blocks(self, (marks[gid] for gid in gids), be)
        inst = np.stack(
            [
                np.arange(marks[gid], marks[gid] + be, dtype=np.int32)
                for gid in gids
            ]
        )
        return gids, member, nblk, inst

    @mirror_guard
    def pipeline_cohort(
        self, gids, values: np.ndarray, active: np.ndarray,
        defer: bool = False,
    ):
        """Advance exactly the cohort ``gids`` one ``BE``-sized round.

        ``values`` is *compact* ``(len(gids), BE, V)`` (row order = cohort
        order), ``active`` ``(len(gids), BE)``.  Non-members neither move
        nor mutate — a cold group is simply not a member of the hot tier's
        dispatch.  On the kernel path the grid is additionally *compacted*
        over the group axis (``core.plan.cohort_blocks`` +
        ``kernels.wirepath.cohort_wirepath_round``): only the group blocks
        containing members are visited, so a one-hot-group tier costs one
        group's work, not G's.  Returns host ``(fresh, inst, value)`` in
        cohort row order — or, with ``defer=True``, a ``_DeferredRound``
        whose ``resolve()`` yields the same triple one wave later
        (DESIGN.md §11); host watermark mirrors advance at dispatch time
        either way.
        """
        gids, member, nblk, inst = self._cohort_prologue(gids, values)
        g = self.cfg.n_groups
        be = values.shape[1]
        self._guard_capacity(gids, be)
        lim = self._reclaim_limits()
        marks = self.next_inst_host
        # the compact mapping is the dispatch plan whether or not the
        # kernel executes it; last_gb reports its fold width on both
        # engines, so introspection never depends on engine choice
        gb, blocks = plan_mod.cohort_blocks(gids, marks, self._fold_width())
        self.last_gb = gb
        self._count(nblk is not None)
        en = jnp.asarray(member)
        if nblk is not None:
            rows, kvals = self._compact_rows(gids, blocks, gb, values)
            self.stack, self.lstate, dfresh, _win, dvalue = self._cohort_k(
                self.stack,
                self.lstate,
                jnp.asarray(np.asarray(blocks, np.int32)),
                self.cstate.next_inst,
                self.cstate.crnd,
                self.alive_mask,
                self.cfg.quorum,
                jnp.asarray(kvals),
                en,
                reclaim_limit=lim,
                group_block=gb,
                window_blocks=nblk,
            )
        else:
            # jnp oracle: full-width dispatch with non-members held inert
            # (round presented as NO_ROUND) — bit-identical results
            vals_f, act_f = plan_mod.scatter_rows(
                gids, values, active, g, self.cfg.value_words
            )
            cs = self.cstate
            eff = CoordinatorState(
                next_inst=cs.next_inst,
                crnd=jnp.where(en != 0, cs.crnd, NO_ROUND),
            )
            _c, self.stack, self.lstate, ffresh, _i, _w, fvalue = self._fused(
                eff,
                self.stack,
                self.lstate,
                jnp.asarray(vals_f),
                jnp.asarray(act_f),
                self.alive_mask,
                self.cfg.quorum,
                reclaim_limit=lim,
            )
            rows = list(gids)
            dfresh, dvalue = ffresh, fvalue
        memj = jnp.asarray(member != 0)
        self.cstate = CoordinatorState(
            next_inst=jnp.where(
                memj, self.cstate.next_inst + be, self.cstate.next_inst
            ),
            crnd=self.cstate.crnd,
        )
        for gid in gids:
            self.next_inst_host[gid] += be
        handle = _DeferredRound(dfresh, dvalue, inst, rows=rows, axis=0)
        return handle if defer else handle.resolve()

    def _compact_rows(self, gids, blocks, gb: int, values: np.ndarray):
        """Compact kernel layout of a cohort's ``(..., M, BE, V)`` rows: row
        ``j*gb + k`` belongs to group ``blocks[j]*gb + k``; rows of
        non-members carry NOP fillers.  Returns ``(rows, kvals)`` with
        ``rows`` each member's compact row, in cohort order."""
        rowof = {
            blk * gb + k: j * gb + k
            for j, blk in enumerate(blocks)
            for k in range(gb)
        }
        shape = values.shape[:-3] + (len(blocks) * gb,) + values.shape[-2:]
        kvals = np.zeros(shape, np.int32)
        kvals[..., 0] = NOP_SENTINEL
        rows = [rowof[gid] for gid in gids]
        kvals[..., rows, :, :] = values
        return rows, kvals

    @mirror_guard
    def pipeline_persistent(
        self, gids, values: np.ndarray, active: np.ndarray,
        defer: bool = False,
    ):
        """Advance the cohort ``gids`` K back-to-back full rounds in ONE
        device dispatch (DESIGN.md §11): the wave descriptor (per-round
        window bases + participation) rides scalar prefetch, the chunk
        queue rides device-resident, and results sync back to host once
        per wave instead of once per round.

        ``values`` is ``(K, len(gids), BE, V)`` — round-major, row order =
        cohort order — and ``active`` ``(K, len(gids), BE)``.  Every member
        participates in every round (the planner only mints K > 1 when each
        member has K full chunks queued), windows are consecutive
        ``BE``-slices from each member's watermark, and delivery is
        bit-identical to K sequential ``pipeline_cohort`` calls.  On the
        kernel engine every round must start and end on a ring block (the
        planner mints K > 1 only then, ``core.plan.blocks_aligned``), so
        that rounds share no block.  Returns host ``(fresh[K, M, BE],
        inst[K, M, BE], value[K, M, BE, V])``, or a ``_DeferredRound``
        with ``defer=True``.
        """
        k, be = values.shape[0], values.shape[2]
        if k * be > self.cfg.n_instances:
            raise ValueError(
                f"persistent wave of {k} x {be} instances would lap the "
                f"{self.cfg.n_instances}-instance ring"
            )
        gids, member, _nblk, _inst0 = self._cohort_prologue(gids, values[0])
        g = self.cfg.n_groups
        marks = self.next_inst_host
        if self.use_kernels and not plan_mod.blocks_aligned(
            self.cfg.n_instances, [marks[gid] for gid in gids], be
        ):
            raise ValueError(
                f"persistent wave of {be}-slot rounds at watermarks "
                f"{[marks[gid] for gid in gids]} is off the ring-block "
                f"boundary ({plan_mod.ring_block(self.cfg.n_instances)} slots)"
            )
        # guard the wave's LAST window up front: an over-watermark wave
        # must fail before any state moves, never mid-wave
        for gid in gids:
            self._reclaim_guard(gid, marks[gid] + (k - 1) * be, be)
        lim = self._reclaim_limits()
        gb, blocks = plan_mod.cohort_blocks(gids, marks, self._fold_width())
        self.last_gb = gb
        self._count(self.use_kernels)
        self.persistent_dispatch_count += 1
        # wave descriptor: cumulative window-base table + participation
        # (rows for non-members are ignored — the kernel substitutes the
        # folded block's lockstep base for them)
        wni = np.zeros((k, g), np.int32)
        wen = np.zeros((k, g), np.int32)
        steps = np.arange(k, dtype=np.int32) * be
        for gid in gids:
            wni[:, gid] = marks[gid] + steps
            wen[:, gid] = 1
        inst = np.stack(
            [
                np.stack(
                    [
                        np.arange(w, w + be, dtype=np.int32)
                        for w in wni[r, gids]
                    ]
                )
                for r in range(k)
            ]
        )
        if self.use_kernels:
            rows, kvals = self._compact_rows(gids, blocks, gb, values)
            self.stack, self.lstate, dfresh, _win, dvalue = self._persist_k(
                self.stack,
                self.lstate,
                jnp.asarray(np.asarray(blocks, np.int32)),
                jnp.asarray(wni),
                jnp.asarray(wen),
                self.cstate.crnd,
                self.alive_mask,
                self.cfg.quorum,
                jnp.asarray(kvals),
                reclaim_limit=lim,
                group_block=gb,
            )
        else:
            # jnp oracle: full-width scatter per round, K-unrolled under
            # one jit — still one dispatch, bit-identical results
            per_round = [
                plan_mod.scatter_rows(
                    gids, values[r], active[r], g, self.cfg.value_words
                )
                for r in range(k)
            ]
            vals_f = np.stack([v for v, _ in per_round])
            act_f = np.stack([a for _, a in per_round])
            _c, self.stack, self.lstate, pfresh, _pi, _pw, pvalue = (
                self._persist_j(
                    self.cstate,
                    self.stack,
                    self.lstate,
                    jnp.asarray(vals_f),
                    jnp.asarray(act_f),
                    self.alive_mask,
                    self.cfg.quorum,
                    enabled_rounds=jnp.asarray(wen != 0),
                    reclaim_limit=lim,
                )
            )
            rows = list(gids)
            dfresh, dvalue = pfresh, pvalue
        memj = jnp.asarray(member != 0)
        self.cstate = CoordinatorState(
            next_inst=jnp.where(
                memj, self.cstate.next_inst + k * be, self.cstate.next_inst
            ),
            crnd=self.cstate.crnd,
        )
        for gid in gids:
            self.next_inst_host[gid] += k * be
        handle = _DeferredRound(dfresh, dvalue, inst, rows=rows, axis=1)
        return handle if defer else handle.resolve()

    @mirror_guard
    def burn_forward(self, gid: int, target: int) -> None:
        """Advance a group's sequencer watermark to ``target`` without
        proposing anything: the skipped instances are NOP holes, never
        decided and recoverable as no-ops (paper §3.1 gap fill).  The
        planner's realignment sweep uses this to bring divergent groups
        back to a common block boundary so the full-width fold re-engages
        (DESIGN.md §8)."""
        self._check_gid(gid)
        if target < self.next_inst_host[gid]:
            raise ValueError(
                f"burn_forward moves only forward: {target} < "
                f"{self.next_inst_host[gid]} (group {gid})"
            )
        self.cstate = CoordinatorState(
            next_inst=self.cstate.next_inst.at[gid].set(target),
            crnd=self.cstate.crnd,
        )
        self.next_inst_host[gid] = target

    # -- per-group liveness and failover -------------------------------------
    def _check_gid(self, gid: int) -> None:
        if not 0 <= gid < self.cfg.n_groups:
            raise ValueError(f"group {gid} out of range [0, {self.cfg.n_groups})")

    def kill_acceptor(self, gid: int, aid: int) -> None:
        self._check_gid(gid)
        self.alive[gid][aid] = False
        self.alive_mask = self.alive_mask.at[gid, aid].set(False)

    def revive_acceptor(self, gid: int, aid: int) -> None:
        self._check_gid(gid)
        self.alive[gid][aid] = True
        self.alive_mask = self.alive_mask.at[gid, aid].set(True)

    def wipe_acceptor(self, gid: int, aid: int) -> None:
        """Crash WITH state loss: zero one acceptor's register rows of one
        group (its BRAM); revival rebuilds from snapshot + live ring suffix
        (``core.failover.restore_acceptor``, DESIGN.md §9)."""
        self._check_gid(gid)
        row = self._slab_row(gid)
        fresh = AcceptorState.init(self.cfg.n_instances, self.cfg.value_words)
        self.stack = jax.tree_util.tree_map(
            lambda s, f: s.at[row, aid].set(f), self.stack, fresh
        )

    @mirror_guard
    def freeze_group(self, gid: int) -> None:
        """Park a group's hardware round at NO_ROUND while a software
        coordinator owns it: every slot the shared dispatch sequences for the
        group is rejected by its acceptors (NO_ROUND < any promised round),
        so nothing is decided and no state mutates — the group is inert in
        the pipeline without recompiling or excluding it."""
        self._check_gid(gid)
        self.cstate = CoordinatorState(
            next_inst=self.cstate.next_inst,
            crnd=self.cstate.crnd.at[gid].set(NO_ROUND),
        )
        self.crnd_host[gid] = NO_ROUND

    @mirror_guard
    def restore_group(self, gid: int, next_inst: int, crnd: int) -> None:
        """Hand a group back to the hardware sequencer at the watermark and
        round the software coordinator reached."""
        self._check_gid(gid)
        self.cstate = CoordinatorState(
            next_inst=self.cstate.next_inst.at[gid].set(next_inst),
            crnd=self.cstate.crnd.at[gid].set(crnd),
        )
        self.next_inst_host[gid] = next_inst
        self.crnd_host[gid] = crnd

    def group_view(self, gid: int) -> _GroupView:
        """The staged single-group surface over group ``gid`` (recovery and
        takeover traffic; the fast path stays in ``pipeline``)."""
        self._check_gid(gid)
        return _GroupView(self, gid)

    # -- dynamic membership: a free-list over the group axis (DESIGN.md §7) --
    def _check_live(self, gid: int) -> None:
        self._check_gid(gid)
        if not self.live_host[gid]:
            raise ValueError(f"group {gid} is retired")

    def live_groups(self) -> list[int]:
        """Currently live group ids, ascending (the routing domain)."""
        return [g for g in range(self.cfg.n_groups) if self.live_host[g]]

    def _slab_row(self, gid: int) -> int:
        """Physical slab row of group ``gid``.  Identity here; the sharded
        subclass translates through its ``PlacementMap`` so every slab
        access (recovery views, wipes, slot resets, ring drains) lands on
        the group's current placement (DESIGN.md §13)."""
        return gid

    def ring_rows(self, gid: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Group ``gid``'s learner ring on the host: the delivered flags,
        the instance decided into each slot and its value words."""
        row = self._slab_row(gid)
        return (
            np.asarray(self.lstate.delivered[row]),
            np.asarray(self.lstate.inst[row]),
            np.asarray(self.lstate.value[row]),
        )

    def _reset_group_slab(self, gid: int) -> None:
        """Zero ONE group's acceptor and learner rings — a fresh tenant's
        slot.  Touches only the group's slab row (the sharded subclass
        re-pins placement before its next fused dispatch, exactly like the
        staged recovery surface)."""
        n, v, a = (
            self.cfg.n_instances,
            self.cfg.value_words,
            self.cfg.n_acceptors,
        )
        row = self._slab_row(gid)
        one = AcceptorState.init(n, v)
        fresh = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (a,) + x.shape), one
        )
        self.stack = jax.tree_util.tree_map(
            lambda s, f: s.at[row].set(f), self.stack, fresh
        )
        self.lstate = jax.tree_util.tree_map(
            lambda s, f: s.at[row].set(f),
            self.lstate,
            batched.LearnerState.init(n, v),
        )

    @mirror_guard
    def create_group(self) -> int:
        """Claim a free slot on the group axis: zeroed rings, fresh
        watermark/round, all acceptors alive.  Deterministic (lowest free
        gid first).  Raises when the service is at capacity."""
        if not self._free:
            raise RuntimeError(
                f"no free group slots (capacity n_groups={self.cfg.n_groups})"
            )
        gid = self._free.pop(0)
        self._reset_group_slab(gid)
        self.live_host[gid] = True
        for aid in range(self.cfg.n_acceptors):
            self.revive_acceptor(gid, aid)
        # fresh sequencer: watermark 0, round 0 (restore_group also resyncs
        # the device/host scalar mirrors, polymorphically per subclass)
        self.restore_group(gid, 0, 0)
        if self.reclaimed_host is not None:
            self.reclaimed_host[gid] = 0
        return gid

    @mirror_guard
    def adopt_group(self, watermark: int) -> int:
        """Claim a free slot for a tenant bootstrapping from a transferred
        snapshot (vertical-Paxos state transfer, DESIGN.md §9): the slot's
        rings are zeroed and both the sequencer watermark and the
        reclamation watermark start at the snapshot's — the history below
        it lives in the ``SnapshotStore``; instances below the watermark
        are never proposed again.  Requires reclamation to be enabled
        (without it a wrapped snapshot watermark has no meaning).  Returns
        the claimed gid."""
        if self.reclaimed_host is None:
            raise ValueError("adopt_group requires reclamation enabled")
        if watermark < 0:
            raise ValueError(f"negative snapshot watermark {watermark}")
        gid = self.create_group()
        self.restore_group(gid, watermark, 0)
        self.reclaimed_host[gid] = watermark
        return gid

    def retire_group(self, gid: int) -> list[tuple[int, bytes]]:
        """Retire a live group: drain its learner ring to a host log, park
        its round at ``NO_ROUND`` (inert in the shared dispatch, exactly
        like freeze), and return the slot to the free-list.  Host scalars
        only — no other group's slab state is touched, and the slabs
        themselves do not move (the slot is zeroed lazily at the next
        ``create_group``).  Returns the drained ``(inst, value_bytes)``
        pairs in instance order — the decided values still resident in the
        retiring group's dedup ring."""
        self._check_live(gid)
        ld, li, lv = self.ring_rows(gid)
        slots = np.nonzero(ld != 0)[0]
        order = slots[np.argsort(li[slots], kind="stable")]
        drained = [(int(li[s]), lv[s].tobytes()) for s in order]
        self.live_host[gid] = False
        self.freeze_group(gid)
        bisect.insort(self._free, gid)
        return drained


class ShardedMultiGroupDataplane(MultiGroupDataplane):
    """``MultiGroupDataplane`` with the group axis partitioned over a device
    mesh (DESIGN.md §6): the ``(G, A, N)`` acceptor rings, ``(G, N)`` learner
    rings and per-group burst slabs shard over a ``groups`` mesh axis via
    ``shard_map``, so the number of device-resident groups scales linearly
    with device count instead of one chip's VMEM/HBM.

    Placement is contiguous slabs: shard ``s`` owns groups
    ``[s*Gl, (s+1)*Gl)`` with ``Gl = G / n_shards``.  Per-group scalar
    control state — the watermark/round vectors and the ``(G, A)`` liveness
    mask — is *host-authoritative* numpy, entering each dispatch replicated;
    ``freeze_group``/``restore_group``/``kill_acceptor`` therefore flip host
    scalars only and reach the owning shard with the next dispatch — no
    global device round-trip, and the big slabs never move.  On a 1-device
    mesh every dispatch reduces bit-exactly to ``MultiGroupDataplane``, so
    the existing parity suites double as its regression net.
    """

    def __init__(
        self,
        cfg: PaxosConfig,
        mesh=None,
        axis: str = "groups",
        use_kernels: bool = False,
    ):
        if mesh is None:
            from repro.launch.mesh import make_group_mesh

            mesh = make_group_mesh()
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
        n_sh = mesh.shape[axis]
        if cfg.n_groups % n_sh:
            raise ValueError(
                f"n_groups={cfg.n_groups} must be divisible by the {axis!r} "
                f"mesh axis size {n_sh}"
            )
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        # big slabs: device-resident, leading group axis sharded over the
        # mesh, each shard built on its own device
        self._slab_sharding = NamedSharding(mesh, P(axis))
        super().__init__(
            cfg, use_kernels=use_kernels, slab_sharding=self._slab_sharding
        )
        self.mesh = mesh
        self.axis = axis
        self.n_shards = n_sh
        self.groups_per_shard = cfg.n_groups // n_sh
        g, a = cfg.n_groups, cfg.n_acceptors
        # host-authoritative scalar control state (mirrors next_inst_host /
        # crnd_host, which the parent already maintains)
        self.cstate = CoordinatorState(
            next_inst=np.zeros((g,), np.int32), crnd=np.zeros((g,), np.int32)
        )
        self.alive_mask = np.ones((g, a), np.int32)
        self._dispatches: dict[tuple[int | None, int], Any] = {}
        self._packed_dispatches: dict[int | None, Any] = {}
        # group -> physical slot permutation (DESIGN.md §13); identity at
        # boot, mutated only by ``migrate_group`` slot swaps.  Device slabs
        # are SLOT-indexed; every host mirror stays gid-indexed and the
        # translation happens exactly once, at the dispatch/slab boundary.
        self._placement = plan_mod.PlacementMap.identity(
            cfg.n_groups, self.groups_per_shard
        )
        # every program a dispatch may run (``core.plan.sharded_programs``),
        # all compiled by ``precompile``
        self.programs = plan_mod.sharded_programs(cfg, n_sh, use_kernels)

    def _fold_width(self) -> int:
        # lockstep folds within one shard's slab (a block has a single ring
        # offset, and a shard sees only its own slab); on a 1-device mesh
        # this is the parent's full-service fold
        cfg = self.cfg
        return plan_mod.fold_cap(
            self.groups_per_shard, cfg.n_instances, cfg.n_acceptors,
            cfg.value_words,
        )

    # -- placement (consumed by serve.ConsensusService) ----------------------
    @property
    def placement(self) -> plan_mod.PlacementMap:
        return self._placement

    def _slab_row(self, gid: int) -> int:
        return self._placement.slot_of[gid]

    def shard_of_group(self, gid: int) -> int:
        """Mesh shard owning group ``gid`` under the current placement."""
        self._check_gid(gid)
        return self._placement.shard_of(gid)

    def group_placement(self) -> list[int]:
        """group id -> owning shard, for the whole service."""
        pm = self._placement
        return [pm.shard_of(g) for g in range(self.cfg.n_groups)]

    def plan_placement(self, loads: Sequence[int]) -> plan_mod.PlacementMap:
        """The load-weighted placement this service *would* adopt for the
        given per-group loads (``PlacementMap.weighted``); pure planning —
        adopting it is a sequence of ``migrate_group`` slot swaps."""
        return plan_mod.PlacementMap.weighted(
            loads, self.n_shards, self.groups_per_shard
        )

    # -- dispatch construction ----------------------------------------------
    def _dispatch(self, nblk: int | None, gb: int):
        """The full-width sharded step for ``nblk`` ring blocks per group
        (``None`` = the jnp engine) at fold width ``gb``."""
        key = (nblk, gb)
        fn = self._dispatches.get(key)
        if fn is None:
            from .fabric import make_sharded_multigroup_round

            fn = make_sharded_multigroup_round(
                self.mesh,
                n_groups=self.cfg.n_groups,
                quorum=self.cfg.quorum,
                axis=self.axis,
                use_kernels=nblk is not None,
                group_block=gb,
                window_blocks=nblk,
            )
            self._dispatches[key] = fn
        return fn

    def _packed_dispatch(self, nblk: int | None):
        fn = self._packed_dispatches.get(nblk)
        if fn is None:
            from .fabric import make_packed_sharded_round

            fn = make_packed_sharded_round(
                self.mesh,
                quorum=self.cfg.quorum,
                axis=self.axis,
                use_kernels=nblk is not None,
                window_blocks=nblk,
            )
            self._packed_dispatches[nblk] = fn
        return fn

    def _ensure_placement(self) -> None:
        # recovery/failover traffic (``group_view``) rewrites one group's
        # slab with gather/scatter updates whose output sharding is
        # unconstrained; re-pin before the next sharded dispatch (a no-op
        # when placement is already correct)
        self.stack = jax.device_put(self.stack, self._slab_sharding)
        self.lstate = jax.device_put(self.lstate, self._slab_sharding)

    def _window(self, nblk: int | None, b: int) -> int | None:
        """The vocabulary's ring blocks for a dispatch whose windows need
        ``nblk`` (``None``: the jnp engine)."""
        if nblk is None:
            return None
        return plan_mod.sharded_window_blocks(self.cfg.n_instances, b)

    def _full_program(
        self, nblk: int | None, slots, marks_slot, b: int
    ) -> plan_mod.ShardedProgram:
        """The full-width program for enabled ``slots`` at slot-ordered
        ``marks_slot``: folded at the cap or not at all on the kernels."""
        gb = 1
        if nblk is not None:
            gb = plan_mod.full_fold(slots, marks_slot, self._fold_width())
        return plan_mod.ShardedProgram("full", self._window(nblk, b), gb, b)

    def _launch_full(self, prog, en_slot, vals_slot, act_slot):
        """Run full-width program ``prog`` over every slab row in slot
        order, rows with ``en_slot`` 0 inert; returns the device
        ``(fresh, inst, value)`` rows in slot order."""
        perm = list(self._placement.group_of)     # slot -> gid
        crnd = np.asarray(self.crnd_host, np.int32)[perm]
        lim = self._reclaim_limits_np()
        if lim is None:
            # full permit: int32.max is unreachable, every lane passes the
            # reclamation gate (legacy overwrite-on-wrap mode)
            lim = np.full((self.cfg.n_groups,), np.iinfo(np.int32).max, np.int32)
        self._ensure_placement()
        fn = self._dispatch(prog.window_blocks, prog.width)
        self.stack, self.lstate, fresh, inst, _win, value = fn(
            np.asarray(self.next_inst_host, np.int32)[perm],
            np.where(en_slot != 0, crnd, NO_ROUND).astype(np.int32),
            en_slot,
            self.alive_mask[perm],
            self.stack,
            self.lstate,
            jnp.asarray(vals_slot),
            jnp.asarray(act_slot),
            reclaim_limit=lim[perm],
        )
        self.last_gb = prog.width
        return fresh, inst, value

    def _launch_packed(self, prog, seg, nip, crp, enp, alp, limp, valsp):
        """Run packed program ``prog`` on per-lane ``(n_shards, C, ...)``
        tables; returns the device ``(fresh, value)`` rows, lane-major."""
        self._ensure_placement()
        fn = self._packed_dispatch(prog.window_blocks)
        self.stack, self.lstate, fresh, _inst, _win, value = fn(
            seg,
            nip,
            crp,
            enp,
            alp,
            self.stack,
            self.lstate,
            jnp.asarray(valsp),
            reclaim_limit=limp,
        )
        self.last_gb = 1               # each lane is its own grid step
        return fresh, value

    def _lane_tables(self, c: int, b: int):
        """Per-lane tables of ``c`` inert pad lanes per shard: no slab row
        of their own, round ``NO_ROUND``, NOP-sentinel values."""
        n_sh, a, v = self.n_shards, self.cfg.n_acceptors, self.cfg.value_words
        valsp = np.zeros((n_sh, c, b, v), np.int32)
        valsp[:, :, :, 0] = NOP_SENTINEL
        return (
            np.zeros((n_sh, c), np.int32),                     # segids
            np.zeros((n_sh, c), np.int32),                     # next_inst
            np.full((n_sh, c), NO_ROUND, np.int32),            # crnd
            np.zeros((n_sh, c), np.int32),                     # enabled
            np.ones((n_sh, c, a), np.int32),                   # alive
            np.full((n_sh, c), np.iinfo(np.int32).max, np.int32),  # limit
            valsp,
        )

    def precompile(self) -> None:
        """Compile every program of ``programs`` before the first dispatch:
        each runs once on the live slabs with every lane inert (round
        ``NO_ROUND``), which leaves the slabs bit-identical and fills the
        jitted functions' own caches, and the learner ring read of a
        snapshot drain runs once."""
        g, v = self.cfg.n_groups, self.cfg.value_words
        # folded full-width programs run their group rows as a loop
        rolled = sum(p.kind == "full" and p.width > 1 for p in self.programs)
        with obs.span("repro.hw.precompile", programs=len(self.programs),
                      rolled=rolled):
            for prog in self.programs:
                b = prog.burst
                if prog.kind == "packed":
                    out = self._launch_packed(prog, *self._lane_tables(prog.width, b))
                else:
                    vals = np.zeros((g, b, v), np.int32)
                    vals[:, :, 0] = NOP_SENTINEL
                    out = self._launch_full(
                        prog, np.zeros((g,), np.int32), vals,
                        np.zeros((g, b), bool),
                    )
                jax.block_until_ready(out)
            self.ring_rows(0)
        self.last_gb = None

    # -- fused fast path: all shards advance their slabs in ONE dispatch ----
    @mirror_guard
    def pipeline(
        self,
        values: np.ndarray,
        active: np.ndarray,
        enabled: list[bool] | None = None,
    ):
        """Same contract (and bit-identical results) as
        ``MultiGroupDataplane.pipeline``, executed as one ``shard_map``
        program over the group slabs."""
        g, b = values.shape[0], values.shape[1]
        enabled, nblk, _ = self._plan_round(b, enabled)
        if not any(enabled):
            return self._empty_round(g, b)
        with obs.span("repro.hw.launch") as sp:
            self._guard_capacity(
                [gid for gid in range(g) if enabled[gid]], b
            )
            pm = self._placement
            # the fold's lockstep blocks are SLOT blocks (the kernel walks
            # physical slab rows), so the width derives from slot-ordered marks
            perm = list(pm.group_of)       # slot -> gid
            marks_slot = [self.next_inst_host[gid] for gid in perm]
            slots = [pm.slot_of[gid] for gid in range(g) if enabled[gid]]
            prog = self._full_program(nblk, slots, marks_slot, b)
            self._count(nblk is not None)
            fresh, inst, value = self._launch_full(
                prog,
                np.asarray(enabled, np.int32)[perm],
                np.asarray(values)[perm],
                np.asarray(active)[perm],
            )
            if obs.enabled():
                sp.set_metadata(members=len(slots), lanes=g, burst=b, full=1)
        with obs.span("repro.hw.readback"):
            rows = list(pm.slot_of)        # gid -> slot: back to gid order
            fresh = np.asarray(fresh)[rows]
            inst = np.asarray(inst)[rows]
            value = np.asarray(value)[rows]
        for gid in range(g):
            if enabled[gid]:
                self.next_inst_host[gid] += b
        self._sync_cstate()
        return fresh, inst, value

    # -- cohort dispatch (DESIGN.md §8), sharded execution -------------------
    @mirror_guard
    def pipeline_cohort(
        self, gids, values: np.ndarray, active: np.ndarray,
        defer: bool = False,
    ):
        """Same contract (and bit-identical results) as the unsharded
        ``pipeline_cohort``, executed as one *packed* ``shard_map`` program
        (DESIGN.md §13).

        A full-width dispatch walks every shard's ``Gl``-row slab with
        non-members held inert, so a cold one-group cohort would pay
        full-width slab cost on every shard.  Packed dispatch restores
        proportional cost under shard_map's shape uniformity via input
        packing: each shard advances ``C`` lanes (``core.plan.packed_lanes``
        of the cohort's max per-shard residency), each lane routed to its
        physical slab row by a ``segids`` table riding scalar prefetch;
        shards with fewer resident members ride inert pad lanes.  A cohort
        whose ``C`` would reach the slab's height runs the full-width fold
        instead: its packed table would visit as many slab rows and pay one
        grid step per lane.  The read-back is eager; with ``defer=True``
        the result comes wrapped as an already-resolved round."""
        be = values.shape[1]
        with obs.span("repro.hw.launch") as sp:
            gids, member, nblk, inst = self._cohort_prologue(gids, values)
            self._guard_capacity(gids, be)
            marks = self.next_inst_host
            pm = self._placement
            n_sh = self.n_shards
            lanes: list[list[int]] = [[] for _ in range(n_sh)]
            for row, gid in enumerate(gids):
                lanes[pm.shard_of(gid)].append(row)
            c = plan_mod.packed_lanes(
                max(len(ls) for ls in lanes), self.groups_per_shard
            )
            self._count(nblk is not None)
            if c is None:
                marks_slot = [marks[gid] for gid in pm.group_of]
                rows = slots = [pm.slot_of[gid] for gid in gids]
                vals_f, act_f = plan_mod.scatter_rows(
                    gids, values, active, self.cfg.n_groups,
                    self.cfg.value_words,
                )
                perm = list(pm.group_of)
                fresh, _inst, value = self._launch_full(
                    self._full_program(nblk, slots, marks_slot, be),
                    np.asarray(member, np.int32)[perm],
                    vals_f[perm],
                    act_f[perm],
                )
            else:
                seg, nip, crp, enp, alp, limp, valsp = self._lane_tables(c, be)
                limnp = self._reclaim_limits_np()
                rows = [0] * len(gids)
                for s in range(n_sh):
                    for j, row in enumerate(lanes[s]):
                        gid = gids[row]
                        seg[s, j] = pm.row_of(gid)
                        enp[s, j] = 1
                        nip[s, j] = marks[gid]
                        crp[s, j] = self.crnd_host[gid]
                        alp[s, j] = self.alive_mask[gid]
                        if limnp is not None:
                            limp[s, j] = limnp[gid]
                        valsp[s, j] = values[row]
                        rows[row] = s * c + j
                prog = plan_mod.ShardedProgram("packed", self._window(nblk, be), c, be)
                fresh, value = self._launch_packed(
                    prog, seg, nip, crp, enp, alp, limp, valsp
                )
            if obs.enabled():
                sp.set_metadata(
                    members=len(gids),
                    lanes=self.cfg.n_groups if c is None else n_sh * c,
                    burst=be, full=int(c is None),
                )
        with obs.span("repro.hw.readback"):
            fresh = np.asarray(fresh)[rows]
            value = np.asarray(value)[rows]
        for gid in gids:
            self.next_inst_host[gid] += be
        self._sync_cstate()
        if defer:
            return _DeferredRound.resolved(fresh, value, inst)
        return fresh, inst, value

    def pipeline_persistent(
        self, gids, values: np.ndarray, active: np.ndarray,
        defer: bool = False,
    ):
        """The documented K=1 fallback (DESIGN.md §11): shard_map needs
        uniform per-shard shapes and host-authoritative control scalars
        enter every dispatch, so the sharded engine executes a persistent
        wave as K sequential cohort dispatches — delivery and numbering
        stay bit-identical to the unsharded wave; only ``dispatch_count``
        (K launches instead of one) and latency differ."""
        k, be = values.shape[0], values.shape[2]
        gids = list(gids)
        if k * be > self.cfg.n_instances:
            raise ValueError(
                f"persistent wave of {k} x {be} instances would lap the "
                f"{self.cfg.n_instances}-instance ring"
            )
        # same up-front whole-wave guard as the unsharded path: fail
        # before any round of the wave mutates state
        marks = self.next_inst_host
        for gid in gids:
            self._reclaim_guard(gid, marks[gid] + (k - 1) * be, be)
        outs = [
            self.pipeline_cohort(gids, values[r], active[r])
            for r in range(k)
        ]
        fresh = np.stack([o[0] for o in outs])
        inst = np.stack([o[1] for o in outs])
        value = np.stack([o[2] for o in outs])
        if defer:
            return _DeferredRound.resolved(fresh, value, inst)
        return fresh, inst, value

    @mirror_guard
    def burn_forward(self, gid: int, target: int) -> None:
        """Host-scalar-only realignment burn (the sharded control-state
        discipline of DESIGN.md §6): the new watermark reaches the owning
        shard with the next dispatch."""
        self._check_gid(gid)
        if target < self.next_inst_host[gid]:
            raise ValueError(
                f"burn_forward moves only forward: {target} < "
                f"{self.next_inst_host[gid]} (group {gid})"
            )
        self.next_inst_host[gid] = target
        self._sync_cstate()

    # -- per-group control: host scalars only, no device round-trip ----------
    def _sync_cstate(self) -> None:
        self.cstate = CoordinatorState(
            next_inst=np.asarray(self.next_inst_host, np.int32),
            crnd=np.asarray(self.crnd_host, np.int32),
        )

    def kill_acceptor(self, gid: int, aid: int) -> None:
        self._check_gid(gid)
        self.alive[gid][aid] = False
        self.alive_mask[gid, aid] = 0

    def revive_acceptor(self, gid: int, aid: int) -> None:
        self._check_gid(gid)
        self.alive[gid][aid] = True
        self.alive_mask[gid, aid] = 1

    @mirror_guard
    def freeze_group(self, gid: int) -> None:
        self._check_gid(gid)
        self.crnd_host[gid] = NO_ROUND
        self._sync_cstate()

    @mirror_guard
    def restore_group(self, gid: int, next_inst: int, crnd: int) -> None:
        self._check_gid(gid)
        self.next_inst_host[gid] = next_inst
        self.crnd_host[gid] = crnd
        self._sync_cstate()

    # -- live slab migration (DESIGN.md §13) ---------------------------------
    @mirror_guard
    def migrate_group(self, gid: int, dst_shard: int) -> None:
        """Move a live tenant's slab to ``dst_shard`` between waves.

        Placement-only state transfer: the caller has already drained the
        group to its reclamation watermark (ring history absorbed into the
        ``SnapshotStore`` — enforced here), so the slab rows carry no
        information the store does not.  The move is then a slot *swap*
        with a vacant (retired) group placed on the destination shard —
        gid keeps its identity (session hashes, log segments and twin
        numbering are placement-blind), only ``_slab_row`` changes:

          1. swap slots with the lowest vacant group on ``dst_shard``;
          2. zero the adopted slot (it holds the vacant group's stale
             retired rows — exactly ``create_group``'s lazy reset);
          3. re-seat the sequencer at the drain watermark.

        No other group's slab state, watermark or placement is touched, so
        the rest of the service keeps dispatching normally around the swap
        — there is no stop-the-world."""
        self._check_live(gid)
        if not 0 <= dst_shard < self.n_shards:
            raise ValueError(
                f"shard {dst_shard} out of range [0, {self.n_shards})"
            )
        if self.reclaimed_host is None:
            raise ValueError("migrate_group requires reclamation enabled")
        wm = self.next_inst_host[gid]
        if self.reclaimed_host[gid] != wm:
            raise ValueError(
                f"group {gid} not drained: reclamation watermark "
                f"{self.reclaimed_host[gid]} != sequencer watermark {wm}"
            )
        pm = self._placement
        if pm.shard_of(gid) == dst_shard:
            return
        vacant = [
            h
            for h in range(self.cfg.n_groups)
            if pm.shard_of(h) == dst_shard and not self.live_host[h]
        ]
        if not vacant:
            raise RuntimeError(
                f"no vacant slot on shard {dst_shard} to migrate group "
                f"{gid} into (retire or migrate a tenant off it first)"
            )
        self._placement = pm.swapped(gid, vacant[0])
        self._reset_group_slab(gid)        # the newly adopted slot
        self._ensure_placement()
        self.restore_group(gid, wm, self.crnd_host[gid])


class PaxosContext:
    """Drop-in replacement context (the paper's ``paxos_ctx``).

    ``use_kernels=None`` picks the engine from the platform
    (``default_use_kernels``): the Pallas wire path on a TPU."""

    def __init__(
        self,
        cfg: PaxosConfig | None = None,
        deliver: Callable[[bytes, int, int], None] | None = None,
        net: SimNet | None = None,
        use_kernels: bool | None = None,
        retransmit_after: int = 3,
        n_learners: int = 1,
        fused: bool = False,
        mesh=None,
        snapshots: bool = False,
    ):
        self.cfg = cfg or PaxosConfig()
        if use_kernels is None:
            use_kernels = default_use_kernels()
        self.deliver_cb = deliver
        self.net = net or SimNet()
        self.n_groups = self.cfg.n_groups
        # the group-keyed surface engages for any multi-group config AND for
        # a sharded single-group one (the sharded dataplane is group-keyed
        # by construction, G = 1 included)
        self.grouped = self.n_groups > 1 or mesh is not None
        if self.grouped:
            # the multi-group service is wire-path only: all groups ride one
            # fused dispatch; staged traffic exists per group for recovery
            # and failover (group views), not as a peer execution mode
            if n_learners != 1:
                raise ValueError(
                    "multi-group context drives the fused wire path and a "
                    "single learner role per group (n_learners must be 1)"
                )
            if mesh is not None:
                # groups-sharded service: the G slabs partition over the
                # mesh's ``groups`` axis (DESIGN.md §6)
                self.hw: HardwareDataplane = ShardedMultiGroupDataplane(  # type: ignore[assignment]
                    self.cfg, mesh=mesh, use_kernels=use_kernels
                )
            else:
                self.hw = MultiGroupDataplane(  # type: ignore[assignment]
                    self.cfg, use_kernels=use_kernels
                )
            self.fused = True
            self._softco_g: dict[int, SoftCoordinator] = {}
            # the group-keyed learn surface
            self.learned_g: list[dict[int, bytes]] = [
                dict() for _ in range(self.n_groups)
            ]
            self._partial_g: list[dict[int, dict[int, tuple[int, bytes]]]] = [
                dict() for _ in range(self.n_groups)
            ]
        else:
            self.hw = HardwareDataplane(self.cfg, use_kernels=use_kernels)
            self.fused = fused
        # the dispatch planner owns burst sizing, cohort tiering and the
        # realignment sweep for the group-keyed pump (DESIGN.md §8); the
        # single-group context is the degenerate one-cohort case and only
        # shares the burst quantizer
        self.planner: plan_mod.DispatchPlanner | None = (
            plan_mod.DispatchPlanner(
                batch=self.cfg.batch,
                n_instances=self.cfg.n_instances,
                realign_after=self.cfg.realign_after,
                persistent_rounds=self.cfg.persistent_rounds,
                sharded=mesh is not None,
            )
            if self.grouped
            else None
        )
        # the per-group delivery log is uniform across context shapes: an
        # ungrouped single-group context logs into group_log[0], so readers
        # (serve.ConsensusService.delivered) never need a G == 1 special case
        self.group_log: list[list[tuple[int, bytes]]] = [
            [] for _ in range(self.n_groups)
        ]
        self._delivered_seqs: set = set()
        self.retransmit_after = retransmit_after
        self.n_learners = n_learners
        # learner state (software role), one per learner
        self.learned: list[dict[int, bytes]] = [dict() for _ in range(n_learners)]
        self._partial: list[dict[int, dict[int, tuple[int, bytes]]]] = [
            dict() for _ in range(n_learners)
        ]
        self.delivered_log: list[tuple[int, bytes]] = []
        # client-seq -> payload; multi-group contexts key by (group, seq) —
        # each group is an independent Paxos, with its own sequence space
        self._pending: dict[Any, _Pending] = {}
        self._next_client_seq = 0
        self._next_client_seq_g = [0] * self.n_groups
        self._next_epoch = 1                      # round-allocator epochs
        self._softco: SoftCoordinator | None = None  # failover coordinator
        # snapshot/compaction subsystem (DESIGN.md §9): when enabled the
        # rings are watermark-gated (no silent overwrite-on-wrap) and
        # ``snapshot_group`` drains the delivered prefix into the store;
        # ``full_group_log`` stitches store prefix + live log uniformly
        self.snapshots: SnapshotStore | None = None
        if snapshots:
            if not self.fused:
                # the drain source is the device learner ring, which only the
                # fused wire path maintains; the staged path's software
                # learners have no ring to reclaim
                raise ValueError(
                    "snapshots require the fused wire path "
                    "(fused=True, or any grouped context)"
                )
            self.snapshots = SnapshotStore()
            self.hw.enable_reclamation()
        self.stats = {"delivered": 0, "retransmits": 0}
        if mesh is not None and use_kernels:
            # the sharded dataplane's programs are a closed list: compile
            # them all before the first submit, none while serving
            self.hw.precompile()

    # -- paper API -----------------------------------------------------------
    def _check_group(self, group: int) -> None:
        if not 0 <= group < self.n_groups:
            raise ValueError(f"group {group} out of range [0, {self.n_groups})")
        if self.grouped and not self.hw.live_host[group]:
            raise ValueError(f"group {group} is retired")

    def submit(self, payload: bytes, group: int = 0) -> int:
        """paxos_submit(ctx, value, size) — ``group`` selects which of the
        device-resident consensus groups sequences the value (0 is the only
        group of a single-group context).

        Oversized payloads are a client error and fail HERE, at the door,
        with the limit named — not downstream at pack time mid-pump, where
        the raise would abort a whole wave of other sessions' traffic."""
        self._check_group(group)
        limit = self.cfg.max_payload_bytes
        if len(payload) > limit:
            raise ValueError(
                f"payload is {len(payload)} bytes but value_words="
                f"{self.cfg.value_words} carries at most {limit} payload "
                f"bytes per value ({self.cfg.value_words * 4}-byte value "
                f"minus the 8-byte seq/len header) — raise "
                f"PaxosConfig.value_words"
            )
        if self.grouped:
            seq = self._next_client_seq_g[group]
            self._next_client_seq_g[group] += 1
            self._pending[(group, seq)] = _Pending(payload, group=group)
        else:
            seq = self._next_client_seq
            self._next_client_seq += 1
            self._pending[seq] = _Pending(payload)
        self.net.send("coordinator", ("submit", seq, payload, group))
        return seq

    def recover(self, inst: int, nop: bytes = b"\x00", group: int = 0) -> None:
        """paxos_recover(ctx, iid, nop_value, size): phase 1+2 with a no-op."""
        self._check_group(group)
        self.net.send("coordinator", ("recover", inst, nop, group))

    # -- event loop ----------------------------------------------------------
    def pump(self, rounds: int = 1) -> None:
        """Drive the fabric: drain submits through the hardware dataplane,
        route votes to learners, fire deliver callbacks, retransmit losses."""
        for _ in range(rounds):
            with obs.span("repro.ctx.pump") as sp:
                submits = self._pump_coordinator()
                self._pump_learners()
                self._retransmit()
                if obs.enabled():
                    sp.set_metadata(submits=submits, pending=len(self._pending))

    def quiescent(self) -> bool:
        """True when nothing is in flight: no pending client sequences and
        no undelivered fabric traffic."""
        return not self._pending and self.net.pending() == 0

    def run_until_quiescent(self, max_rounds: int = 64) -> None:
        for _ in range(max_rounds):
            if self.quiescent():
                return
            self.pump()

    # -- internals -----------------------------------------------------------
    def _pump_coordinator(self) -> int:
        """Run the coordinator over its inbox; returns the submits taken."""
        inbox = self.net.recv_all("coordinator")
        submits = [
            (m[1], m[2], m[3] if len(m) > 3 else 0)
            for m in inbox
            if m[0] == "submit"
        ]
        recovers = [
            (m[1], m[2], m[3] if len(m) > 3 else 0)
            for m in inbox
            if m[0] == "recover"
        ]
        if self.grouped:
            self._pump_coordinator_groups(submits, recovers)
            return len(submits)

        for inst, nop, _gid in recovers:
            self._run_recover(inst, nop)
        submits = [(seq, payload) for seq, payload, _gid in submits]

        b = self.cfg.batch
        for i in range(0, len(submits), b):
            chunk = submits[i : i + b]
            with obs.span("repro.ctx.chunk") as sp:
                traced = obs.enabled()
                if traced:
                    t_chunk = time.perf_counter()
                with obs.span("repro.ctx.pack"):
                    # the fused path right-sizes the burst on BOTH engines
                    # (engine-agnostic quantization, core.plan); the staged
                    # path keeps the full batch.
                    be = self._burst_size(len(chunk)) if self.fused else b
                    vals, active = self._pack_chunk(chunk, be)
                if traced:
                    sp.set_metadata(
                        ops=len(chunk), burst=be,
                        wait_us=self._queue_wait_us(chunk, t_chunk),
                    )
                if self.fused and self._softco is None:
                    # the CAANS wire path: the whole Phase-2 round below the
                    # host boundary, one dispatch — votes never surface as
                    # messages
                    fresh, inst, value = self.hw.pipeline(vals, active)
                    self._deliver_burst(fresh, inst, value)
                    continue
                if self._softco is not None:
                    p2a = self._soft_sequence(vals, active)
                else:
                    p2a = self.hw.sequence(vals, active)
                votes = self.hw.vote(p2a)
                for aid, v in enumerate(votes):
                    if v is None:
                        continue
                    for lid in range(self.n_learners):
                        self.net.send(("learner", lid), ("votes", aid, _to_host(v)))
        return len(submits)

    def _queue_wait_us(self, chunk: list[tuple[int, bytes]], t: float) -> float:
        """Microseconds the chunk's ops waited from submit to ``t``, summed
        (a resent op whose first copy was delivered waits no more)."""
        pending = self._pending
        return 1e6 * sum(
            t - pending[seq].t_submit for seq, _ in chunk if seq in pending
        )

    def _deliver_burst(self, fresh, inst, value) -> None:
        """Deliver a fused dispatch's fresh lanes, in lane order."""
        with obs.span("repro.ctx.deliver") as sp:
            n0 = self.stats["delivered"]
            for j in range(len(fresh)):
                if not fresh[j]:
                    continue
                raw = value[j].tobytes()
                for lid in range(self.n_learners):
                    if int(inst[j]) not in self.learned[lid]:
                        self.learned[lid][int(inst[j])] = raw
                self._deliver(int(inst[j]), raw)
            if obs.enabled():
                sp.set_metadata(delivered=self.stats["delivered"] - n0)

    def _pump_learners(self) -> None:
        for lid in range(self.n_learners):
            for m in self.net.recv_all(("learner", lid)):
                _, aid, votes = m
                self._learn(lid, aid, votes)

    def _learn(self, lid: int, aid: int, votes: dict) -> None:
        self._quorum_learn(
            self.learned[lid],
            self._partial[lid],
            aid,
            votes,
            self._deliver if lid == 0 else None,
        )

    def _quorum_learn(
        self,
        learned: dict[int, bytes],
        partial: dict[int, dict[int, tuple[int, bytes]]],
        aid: int,
        votes: dict,
        deliver: Callable[[int, bytes], None] | None,
    ) -> None:
        """The software learner: fold one acceptor's vote batch into the
        partial-quorum table; at quorum, record the decision and (when this
        learner delivers) fire ``deliver(inst, raw)``.  Shared by the
        per-learner and per-group learn surfaces."""
        quorum = self.cfg.quorum
        for i in range(len(votes["msgtype"])):
            if votes["msgtype"][i] != MSG_P2B:
                continue
            inst = int(votes["inst"][i])
            if inst in learned:
                continue  # duplicate suppression
            slot = partial.setdefault(inst, {})
            slot[aid] = (int(votes["vrnd"][i]), votes["value"][i].tobytes())
            by_rnd: dict[int, int] = {}
            for vr, _ in slot.values():
                by_rnd[vr] = by_rnd.get(vr, 0) + 1
            for vr, cnt in by_rnd.items():
                if cnt >= quorum:
                    raw = next(v for r, v in slot.values() if r == vr)
                    learned[inst] = raw
                    partial.pop(inst, None)
                    if deliver is not None:
                        deliver(inst, raw)
                    break

    # -- multi-group internals (G device-resident groups, fused dispatch) ----
    def _pump_coordinator_groups(
        self,
        submits: list[tuple[int, bytes, int]],
        recovers: list[tuple[int, bytes, int]],
    ) -> None:
        """Group-keyed coordinator pump: recovery first, then groups under a
        software coordinator (staged, per group), then one fused multi-group
        dispatch per burst for everything hardware-sequenced."""
        # traffic addressed to a retired group is dropped at the door: the
        # slot may already belong to the free-list (or a future tenant), and
        # a retired group must never sequence — in-flight submits died with
        # the tenant (clients re-route at the membership epoch bump)
        live = self.hw.live_host
        submits = [s for s in submits if live[s[2]]]
        recovers = [r for r in recovers if live[r[2]]]
        for inst, nop, gid in recovers:
            self._run_recover_group(gid, inst, nop)
        queues: list[list[tuple[int, bytes]]] = [
            [] for _ in range(self.n_groups)
        ]
        for seq, payload, gid in submits:
            queues[gid].append((seq, payload))
        b = self.cfg.batch

        for gid in list(self._softco_g):
            q, queues[gid] = queues[gid], []
            for i in range(0, len(q), b):
                be = self._burst_size(len(q[i : i + b]))
                vals, active = self._pack_chunk(q[i : i + b], be)
                p2a = self._soft_sequence_group(gid, vals, active)
                for aid, v in enumerate(self.hw.group_view(gid).vote(p2a)):
                    if v is not None:
                        # learners route on the header's group id, not on
                        # ambient context — the switch model (paper Fig. 5)
                        self._learn_group(int(v.gid), aid, _to_host(v))

        # the whole service advances together, tiered by the dispatch
        # planner (DESIGN.md §8): each chunk wave partitions the loaded
        # groups into cohorts — one dispatch per distinct right-sized
        # burst, hot cohorts at the full block-aligned batch, cold cohorts
        # coalesced into a shared small burst — instead of padding every
        # cold group up to the hottest group's burst.  Frozen (software-
        # coordinated), vacant and idle groups are simply not members of
        # any cohort: they burn no ring instances and stay bit-identical
        # to not being pumped.  Burst sizes are engine-agnostic, so every
        # backend — and G independent per-group oracles — resolves the
        # wave identically.
        # The wave loop is double-buffered (DESIGN.md §11) when
        # ``cfg.async_pump``: wave N's host read-back is deferred until
        # wave N+1 has been dispatched, so host planning/packing overlaps
        # device execution.  Planning reads only host mirrors (advanced at
        # dispatch time), never a resolve, and every in-flight wave is
        # drained before pump() returns — the pump stays externally
        # synchronous, with delivery order identical to the serial loop.
        # A cohort planned as a K-round persistent wave consumes K - 1
        # further batch-sized slices from its members' queues and rides
        # ONE dispatch (``pipeline_persistent``).
        hw = self.hw
        async_on = self.cfg.async_pump
        in_flight: list[tuple[tuple[int, ...], Any]] = []
        while any(queues):
            pending = [len(q) for q in queues]
            chunks = [q[:b] for q in queues]
            queues = [q[b:] for q in queues]
            with obs.span("repro.plan.round") as sp:
                rp = self.planner.plan_round(
                    [len(c) for c in chunks],
                    hw.next_inst_host,
                    hw.live_host,
                    hw.crnd_host,
                    pending=pending,
                )
                if obs.enabled():
                    sp.set_metadata(
                        cohorts=len(rp.cohorts),
                        members=sum(len(c.gids) for c in rp.cohorts),
                    )
            for gid, target in rp.realign:
                hw.burn_forward(gid, target)
            wave: list[tuple[tuple[int, ...], Any]] = []
            for cohort in rp.cohorts:
                kk = self._wave_depth_clamped(cohort)
                if kk > 1:
                    rounds = [[chunks[gid] for gid in cohort.gids]]
                    for _ in range(kk - 1):
                        rounds.append(
                            [queues[gid][:b] for gid in cohort.gids]
                        )
                        for gid in cohort.gids:
                            queues[gid] = queues[gid][b:]
                    packed = [
                        [self._pack_chunk(c, cohort.burst) for c in row]
                        for row in rounds
                    ]
                    vals = np.stack(
                        [np.stack([v for v, _ in row]) for row in packed]
                    )
                    act = np.stack(
                        [np.stack([a for _, a in row]) for row in packed]
                    )
                    handle = hw.pipeline_persistent(
                        cohort.gids, vals, act, defer=True
                    )
                else:
                    packed = [
                        self._pack_chunk(chunks[gid], cohort.burst)
                        for gid in cohort.gids
                    ]
                    vals = np.stack([v for v, _ in packed])
                    act = np.stack([a for _, a in packed])
                    handle = hw.pipeline_cohort(
                        cohort.gids, vals, act, defer=True
                    )
                wave.append((cohort.gids, handle))
            if async_on:
                # this wave is in flight: resolve and deliver the PREVIOUS
                # wave while the device works on this one
                for gids_, handle in in_flight:
                    self._resolve_wave(gids_, handle)
                in_flight = wave
            else:
                for gids_, handle in wave:
                    self._resolve_wave(gids_, handle)
        for gids_, handle in in_flight:
            self._resolve_wave(gids_, handle)

    def _wave_depth_clamped(self, cohort: plan_mod.Cohort) -> int:
        """The pump-side clamp on a cohort's planned wave depth: reclaim
        headroom (instances until the first unreclaimed slot) may cap K
        below the planner's choice.  Host-scalar arithmetic on mirrors that
        are identical across backends, so every engine clamps identically;
        chunks beyond the clamp simply stay queued for the next wave."""
        kk = cohort.rounds
        if kk <= 1:
            return kk
        lim = self.hw._reclaim_limits_np()
        if lim is not None:
            for gid in cohort.gids:
                head = (
                    int(lim[gid]) - self.hw.next_inst_host[gid]
                ) // cohort.burst
                kk = min(kk, head)
        return max(1, kk)

    def _resolve_wave(self, gids: tuple[int, ...], handle: Any) -> None:
        """Host read-back + delivery for one dispatched cohort wave.
        Persistent waves deliver rounds-then-rows — exactly the order K
        sequential single-round dispatches would have produced."""
        fresh, inst, value = handle.resolve()
        if fresh.ndim == 2:            # single-round wave: (M, BE)
            fresh, inst, value = fresh[None], inst[None], value[None]
        for r in range(fresh.shape[0]):
            for row, gid in enumerate(gids):
                for j in range(fresh.shape[2]):
                    if not fresh[r, row, j]:
                        continue
                    raw = value[r, row, j].tobytes()
                    ii = int(inst[r, row, j])
                    if ii not in self.learned_g[gid]:
                        self.learned_g[gid][ii] = raw
                    self._deliver_group(gid, ii, raw)

    def _burst_size(self, longest: int) -> int:
        """Wire-burst sizing, engine-agnostic (``core.plan.quantize_burst``):
        the jnp oracle and the Pallas kernel path see identical burst
        shapes, so burst sizing can never fork the backends' delivery logs;
        pow2 quantization bounds both the NOP-filler waste and the jit
        cache (one compiled program per distinct shape)."""
        be = plan_mod.quantize_burst(longest, self.cfg.batch)
        if self.planner is not None:
            self.planner.note_burst(be)
        return be

    def _pack_chunk(
        self, chunk: list[tuple[int, bytes]], be: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Pack (seq, payload) pairs into a (BE, V) wire burst; unfilled
        slots carry the NOP sentinel and are inactive."""
        return plan_mod.pack_rows(
            [self._encode(seq, payload) for seq, payload in chunk],
            be,
            self.cfg.value_words,
        )

    def _soft_sequence_group(
        self, gid: int, vals: np.ndarray, active: np.ndarray
    ) -> MsgBatch:
        return self._soft_p2a(self._softco_g[gid], vals, active, gid=gid)

    def _learn_group(self, gid: int, aid: int, votes: dict) -> None:
        """Per-group software learner (staged traffic: failover, recovery)."""
        self._quorum_learn(
            self.learned_g[gid],
            self._partial_g[gid],
            aid,
            votes,
            functools.partial(self._deliver_group, gid),
        )

    def _deliver_group(self, gid: int, inst: int, raw: bytes) -> None:
        self._deliver_value(inst, raw, group=gid)

    def _run_recover_group(self, gid: int, inst: int, nop: bytes) -> None:
        """Per-group recovery: the shared engine against one group's view,
        learning decided votes directly into the group's learn surface."""
        votes = self._recover_votes(self.hw.group_view(gid), inst, nop, gid=gid)
        for aid, v in enumerate(votes or []):
            if v is not None:
                self._learn_group(int(v.gid), aid, _to_host(v))

    def _deliver(self, inst: int, raw: bytes) -> None:
        self._deliver_value(inst, raw)

    def _deliver_value(
        self, inst: int, raw: bytes, group: int | None = None
    ) -> None:
        """The delivery contract, shared by the single-group and group-keyed
        paths: discard internal fillers, suppress duplicates (retransmit
        decided twice — paper §3.1), settle the pending entry, log, and fire
        the application callback.  ``group`` selects the per-group sequence
        space and delivery log."""
        words = np.frombuffer(raw, "<i4")
        if words[0] == NOP_SENTINEL:
            return  # internal filler — discarded by the library
        seq = int(words[0])
        key: Any = seq if group is None else (group, seq)
        if key in self._delivered_seqs:
            return
        self._delivered_seqs.add(key)
        payload = raw[8 : 8 + int(words[1])]
        self._pending.pop(key, None)
        self.delivered_log.append((inst, payload))
        self.group_log[0 if group is None else group].append((inst, payload))
        self.stats["delivered"] += 1
        if self.deliver_cb:
            self.deliver_cb(payload, len(payload), inst)

    def _retransmit(self) -> None:
        with obs.span("repro.ctx.retransmit") as sp:
            if obs.enabled():
                sp.set_metadata(pending=len(self._pending))
            for key, p in list(self._pending.items()):
                p.age += 1
                if p.age >= self.retransmit_after:
                    p.age = 0
                    self.stats["retransmits"] += 1
                    seq = key[1] if isinstance(key, tuple) else key
                    self.net.send("coordinator", ("submit", seq, p.payload, p.group))

    def _encode(self, seq: int, payload: bytes) -> np.ndarray:
        nbytes = self.cfg.value_words * 4
        if len(payload) > nbytes - 8:
            raise ValueError(
                f"value too large: {len(payload)} > {nbytes - 8} "
                f"(increase PaxosConfig.value_words)"
            )
        head = np.array([seq, len(payload)], np.int32).tobytes()
        return np.frombuffer((head + payload).ljust(nbytes, b"\x00"), "<i4").copy()

    # -- snapshot / compaction (DESIGN.md §9) --------------------------------
    def _require_snapshots(self) -> SnapshotStore:
        if self.snapshots is None:
            raise ValueError(
                "snapshots are not enabled on this context "
                "(construct with snapshots=True)"
            )
        return self.snapshots

    def full_group_log(self, gid: int = 0) -> list[tuple[int, bytes]]:
        """The group's complete delivery history: compacted snapshot prefix
        (if any) stitched before the live ``group_log`` — the ONE read that
        is uniform in steady state, at retirement, and after restore."""
        return self.group_log_since(gid, 0)

    def group_log_since(
        self, gid: int = 0, start: int = 0
    ) -> list[tuple[int, bytes]]:
        """``full_group_log(gid)[start:]`` as a new list, without building
        the whole stitched history: O(entries past ``start``).  Indices are
        stable under compaction — ``snapshot_group`` moves the live log's
        head into the prefix without reordering — so an apply cursor can
        read from its watermark across any number of compactions."""
        live = self.group_log[gid]
        if self.snapshots is None:
            return live[start:]
        prefix = self.snapshots.log_prefix(gid)
        if start >= len(prefix):
            return live[start - len(prefix):]
        return prefix[start:] + live

    def snapshot_group(
        self, gid: int = 0, upto: int | None = None
    ) -> GroupSnapshot:
        """Drain group ``gid``'s decided ring prefix below ``upto`` (default:
        its sequencer watermark — everything) into the ``SnapshotStore``,
        seal it, move the host-log prefix into the store (compaction), and
        advance the reclamation watermark so the drained ring slots may be
        re-sequenced.  Returns the group's sealed ``GroupSnapshot``.
        """
        with obs.span("repro.snapshot.drain") as sp:
            store = self._require_snapshots()
            self._check_group(gid)
            hw = self.hw
            if self.grouped:
                seq_mark = hw.next_inst_host[gid]
                ld, li, lv = hw.ring_rows(gid)
            else:
                seq_mark = hw._next_inst_host
                ld = np.asarray(hw.lstate.delivered)
                li = np.asarray(hw.lstate.inst)
                lv = np.asarray(hw.lstate.value)
            upto = seq_mark if upto is None else upto
            wm = store.watermark(gid)
            if not wm <= upto <= seq_mark:
                raise ValueError(
                    f"snapshot upto={upto} outside [{wm}, {seq_mark}] "
                    f"(group {gid})"
                )
            # decided entries in [wm, upto), ascending by instance — the raw
            # ring words (NOP fillers included: the seal covers device history)
            slots = np.nonzero((ld != 0) & (li >= wm) & (li < upto))[0]
            order = slots[np.argsort(li[slots], kind="stable")]
            if obs.enabled():
                sp.set_metadata(entries=len(order))
            store.absorb(gid, li[order], lv[order], upto)
            # compaction: move the host log's leading run below the watermark
            # into the store (list order preserved exactly — stitched reads are
            # bit-identical to the unsplit log)
            log = self.group_log[gid]
            cut = 0
            while cut < len(log) and log[cut][0] < upto:
                cut += 1
            store.absorb_log(gid, log[:cut])
            self.group_log[gid] = log[cut:]
            if self.grouped:
                hw.set_reclaimed(gid, upto)
            else:
                hw.set_reclaimed(upto)
            return store.snapshot(gid)

    def crash_acceptor(self, aid: int, group: int = 0) -> None:
        """Crash one group member WITH state loss: liveness drops AND its
        acceptor register file (BRAM) is zeroed — unlike ``kill_acceptor``,
        which models a frozen-but-intact switch.  Revive with
        ``restore_acceptor`` (snapshot + live ring suffix bootstrap)."""
        self._check_group(group)
        if self.grouped:
            self.hw.kill_acceptor(group, aid)
            self.hw.wipe_acceptor(group, aid)
        else:
            self.hw.kill_acceptor(aid)
            self.hw.wipe_acceptor(aid)

    def restore_acceptor(self, aid: int, group: int = 0) -> int:
        """Revive a crashed group member by state transfer (DESIGN.md §9):
        instances below the snapshot watermark are covered by the sealed
        snapshot (never re-proposed), and the live ring suffix's decided
        instances are adopted from the learner ring — they are decided, so
        claiming votes for them at the current round is safe (the vertical-
        Paxos transfer NetChain motivates).  Returns the number of adopted
        ring slots."""
        from .failover import restore_acceptor as _restore

        self._check_group(group)
        wm = self.snapshots.watermark(group) if self.snapshots else 0
        if self.grouped:
            return _restore(self.hw, aid, gid=group, watermark=wm)
        return _restore(self.hw, aid, watermark=wm)

    def adopt_group(
        self,
        snap: GroupSnapshot,
        log_prefix: list[tuple[int, bytes]] | None = None,
    ) -> int:
        """Admit a tenant bootstrapping from a transferred snapshot: claims
        a free slot whose sequencer and reclamation watermarks start at
        ``snap.watermark``, and seeds the ``SnapshotStore`` from the
        transfer — verifying its seal (divergence/corruption check) before
        trusting it.  ``log_prefix`` seeds the stitched ``delivered()``
        history.  Returns the new group id."""
        self._require_grouped()
        store = self._require_snapshots()
        gid = self.hw.adopt_group(int(snap.watermark))
        self.learned_g[gid] = {}
        self._partial_g[gid] = {}
        self.group_log[gid] = []
        self._next_client_seq_g[gid] = 0
        store.reset_group(gid)
        store.seed(gid, snap, log_prefix)
        return gid

    def migrate_group(
        self, gid: int, dst_shard: int, max_rounds: int = 64
    ) -> GroupSnapshot:
        """Live slab migration (DESIGN.md §13): move tenant ``gid`` to
        ``dst_shard`` between waves, no stop-the-world.

        The protocol composes machinery this context already trusts:
        pump until the group's in-flight submissions drain (other tenants
        keep deciding during these waves), ``snapshot_group`` the full
        prefix (ring drained into the ``SnapshotStore``, reclamation
        watermark advanced to the sequencer watermark), seal it, let the
        sharded dataplane swap slots, then re-derive the store's seal and
        verify it against the pre-move snapshot — the same
        divergence/corruption check ``adopt_group`` applies to transferred
        state.  Returns the sealed snapshot the move was verified against.
        Callers routing by placement must bump their routing epoch
        (``serve.ConsensusService.migrate_group`` does)."""
        self._require_grouped()
        store = self._require_snapshots()
        self._check_group(gid)
        hw = self.hw
        if not hasattr(hw, "migrate_group"):
            raise ValueError(
                "migrate_group requires the groups-sharded dataplane "
                "(construct the context with mesh=...)"
            )
        for _ in range(max_rounds):
            if not any(
                isinstance(k, tuple) and k[0] == gid for k in self._pending
            ):
                break
            self.pump()
        else:
            raise RuntimeError(
                f"group {gid} did not drain within {max_rounds} pump rounds"
            )
        snap = self.snapshot_group(gid)
        hw.migrate_group(gid, dst_shard)
        after = store.snapshot(gid)
        if after.seal != snap.seal or after.watermark != snap.watermark:
            raise RuntimeError(
                f"group {gid} snapshot seal changed across migration: "
                f"{snap.seal!r} -> {after.seal!r}"
            )
        return snap

    # -- dynamic membership (DESIGN.md §7) -----------------------------------
    def _require_grouped(self) -> None:
        if not self.grouped:
            raise ValueError(
                "dynamic membership requires a group-keyed context "
                "(n_groups > 1 or mesh=...)"
            )

    def live_groups(self) -> list[int]:
        """Currently live group ids (ascending) — the routing domain."""
        if not self.grouped:
            return [0]
        return self.hw.live_groups()

    def create_group(self) -> int:
        """Admit a tenant: claim a free slot on the group axis (zeroed
        rings, fresh watermark/round and client-sequence space, empty
        logs).  Returns the new group id — deterministic, lowest free slot
        first."""
        self._require_grouped()
        gid = self.hw.create_group()
        self.learned_g[gid] = {}
        self._partial_g[gid] = {}
        self.group_log[gid] = []
        self._next_client_seq_g[gid] = 0
        if self.snapshots is not None:
            self.snapshots.reset_group(gid)
        return gid

    def retire_group(self, gid: int) -> list[tuple[int, bytes]]:
        """Reclaim a tenant's slot: the group's delivery log is drained
        (returned to the caller — the serving tier archives it for routing-
        epoch stitching), its round parks at ``NO_ROUND`` and the slot joins
        the free-list.  Undelivered submissions to the group are dropped —
        with the tenant gone there is no group to decide them — and their
        dedup keys are purged so a future tenant reusing the slot starts
        from a clean (group, seq) space.  Host scalars only: no other
        group's state is touched.  With snapshots enabled the returned log
        is the STITCHED history (compacted prefix + live log) — retirement
        and steady state read the same way."""
        self._require_grouped()
        self.hw.retire_group(gid)          # raises unless live
        self._softco_g.pop(gid, None)
        # flush the tenant's in-flight coordinator traffic NOW, not at the
        # next pump: if the slot is recreated before a pump runs, the
        # pump-time liveness filter would see the recycled slot live again
        # and sequence the old tenant's stale submit into the new tenant's
        # log (and poison its fresh (group, seq) dedup space)
        self.net.purge(
            "coordinator", lambda m: (m[3] if len(m) > 3 else 0) == gid
        )
        for key in [
            k
            for k in self._pending
            if isinstance(k, tuple) and k[0] == gid
        ]:
            del self._pending[key]
        self._delivered_seqs = {
            k
            for k in self._delivered_seqs
            if not (isinstance(k, tuple) and k[0] == gid)
        }
        return self.full_group_log(gid)

    # -- failover ------------------------------------------------------------
    def fail_coordinator(
        self, est_next_inst: int | None = None, group: int = 0
    ) -> None:
        """Hardware coordinator dies; a software coordinator takes over.

        Runs the *safe* takeover (core.failover): claims a globally unique
        higher round, Phase-1-scans the uncertainty window around the
        (possibly stale) sequencer estimate, re-proposes any voted values it
        finds, and resumes sequencing past them — the paper's §3.1/§6.4
        procedure with the catch-up made explicit.

        On a multi-group context this is a *per-group* event: only ``group``
        moves to software coordination (its hardware round parks at NO_ROUND,
        making it inert in the shared fused dispatch); every other group keeps
        hardware-sequencing undisturbed.
        """
        self._check_group(group)
        if self.grouped:
            return self._fail_coordinator_group(group, est_next_inst)

        from .failover import takeover

        est = (
            est_next_inst
            if est_next_inst is not None
            else int(jax.device_get(self.hw.cstate.next_inst))
        )
        epoch = self._next_epoch
        self._next_epoch += 1
        res = takeover(
            self.hw,
            coordinator_id=1,
            epoch=epoch,
            est_next_inst=est,
            window=self.cfg.batch * 2,
            quorum=self.cfg.quorum,
        )
        self._softco = SoftCoordinator(
            cid=1, crnd=res.crnd, next_inst=res.next_inst
        )
        return res

    def _fail_coordinator_group(
        self, gid: int, est_next_inst: int | None
    ) -> None:
        from .failover import takeover_group

        est = (
            est_next_inst
            if est_next_inst is not None
            else int(jax.device_get(self.hw.cstate.next_inst[gid]))
        )
        epoch = self._next_epoch
        self._next_epoch += 1
        res = takeover_group(
            self.hw,
            gid,
            coordinator_id=1,
            epoch=epoch,
            est_next_inst=est,
            window=self.cfg.batch * 2,
            quorum=self.cfg.quorum,
        )
        self._softco_g[gid] = SoftCoordinator(
            cid=1, crnd=res.crnd, next_inst=res.next_inst
        )
        self.hw.freeze_group(gid)
        return res

    @mirror_guard
    def restore_hardware_coordinator(self, group: int = 0) -> None:
        self._check_group(group)
        if self.grouped:
            co = self._softco_g.pop(group, None)
            if co is not None:
                # only this group's watermark/round move
                self.hw.restore_group(group, int(co.next_inst), int(co.crnd))
            return
        if self._softco is None:
            return
        nxt = int(self._softco.next_inst)
        self.hw.cstate = CoordinatorState(
            next_inst=jnp.int32(nxt),
            crnd=jnp.int32(self._softco.crnd),
        )
        self.hw._next_inst_host = nxt  # resync the host watermark mirror
        self._softco = None

    def _soft_sequence(self, vals: np.ndarray, active: np.ndarray) -> MsgBatch:
        assert self._softco is not None
        return self._soft_p2a(self._softco, vals, active)

    def _soft_p2a(
        self, co: SoftCoordinator, vals: np.ndarray, active: np.ndarray,
        gid: int | None = None,
    ) -> MsgBatch:
        """Software-coordinator sequencing: bind a burst to the coordinator's
        next window (shared by the single-group and per-group failover
        paths; ``gid`` tags the batch with its consensus group)."""
        b = vals.shape[0]
        inst = np.arange(co.next_inst, co.next_inst + b, dtype=np.int32)
        co.next_inst += b
        return MsgBatch(
            msgtype=jnp.where(jnp.asarray(active), MSG_P2A, MSG_NOP).astype(jnp.int32),
            inst=jnp.asarray(inst),
            rnd=jnp.full((b,), co.crnd, jnp.int32),
            vrnd=jnp.full((b,), NO_ROUND, jnp.int32),
            swid=jnp.full((b,), co.cid, jnp.int32),
            value=jnp.asarray(vals),
            gid=None if gid is None else jnp.int32(gid),
        )

    def _run_recover(self, inst: int, nop: bytes) -> None:
        """Phase 1 + Phase 2 for one instance with a no-op value (paper §3.1);
        decided votes fan out to the software learners over SimNet."""
        votes = self._recover_votes(self.hw, inst, nop)
        for aid, v in enumerate(votes or []):
            if v is None:
                continue
            for lid in range(self.n_learners):
                self.net.send(("learner", lid), ("votes", aid, _to_host(v)))

    def _recover_votes(
        self, surface, inst: int, nop: bytes, gid: int | None = None
    ) -> list[MsgBatch | None] | None:
        """The shared recovery engine: Phase-1 scan one instance, choose the
        required value (discovered vote, else the no-op), Phase-2 it, and
        return the per-acceptor vote batches (None = no quorum of promises).
        ``surface`` is any staged dataplane surface — the hardware dataplane
        or one group's view; ``gid`` tags the batches with their group.
        """
        from .failover import allocate_round

        epoch = self._next_epoch
        self._next_epoch += 1
        crnd = allocate_round(epoch, coordinator_id=2)
        b = self.cfg.batch
        gtag = None if gid is None else jnp.int32(gid)
        # Filler slots carry a contiguous inst window starting at the target:
        # the vectorized acceptor scatter requires distinct ring slots per
        # batch, and all-zero filler insts would collide with the recovered
        # instance whenever inst % n_instances == 0 (slot-0 clobber).  The
        # fillers' rnd stays NO_ROUND, so they never accept/promise anything.
        window = jnp.arange(inst, inst + b, dtype=jnp.int32)
        p1a = MsgBatch.nop(b, self.cfg.value_words)
        p1a = p1a.replace(
            msgtype=p1a.msgtype.at[0].set(MSG_P1A),
            inst=window,
            rnd=p1a.rnd.at[0].set(crnd),
            gid=gtag,
        )
        promises = surface.prepare(p1a)
        best: tuple[int, bytes | None] = (NO_ROUND, None)
        got = 0
        for v in promises:
            if v is None:
                continue
            host = _to_host(v)
            if host["msgtype"][0] != 2:  # MSG_P1B
                continue
            got += 1
            vr = int(host["vrnd"][0])
            if vr > best[0]:
                best = (vr, host["value"][0].tobytes())
        if got < self.cfg.quorum:
            return None  # cannot recover without a quorum
        if best[1] is not None and best[0] != NO_ROUND:
            value_words = np.frombuffer(best[1], "<i4").copy()
        else:
            value_words = self._encode(-1, nop)
            value_words[0] = NOP_SENTINEL
        p2a = MsgBatch.nop(b, self.cfg.value_words)
        p2a = p2a.replace(
            msgtype=p2a.msgtype.at[0].set(MSG_P2A),
            inst=window,  # distinct slots; fillers at NO_ROUND never accept
            rnd=p2a.rnd.at[0].set(crnd),
            value=p2a.value.at[0].set(jnp.asarray(value_words)),
            gid=gtag,
        )
        return surface.vote(p2a)


def _to_host(m: MsgBatch) -> dict:
    return {
        "msgtype": np.asarray(m.msgtype),
        "inst": np.asarray(m.inst),
        "rnd": np.asarray(m.rnd),
        "vrnd": np.asarray(m.vrnd),
        "swid": np.asarray(m.swid),
        "value": np.asarray(m.value),
    }
