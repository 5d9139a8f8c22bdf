"""Persistent K-round waves + the double-buffered pump (DESIGN.md §11).

Four layers of the same bit-exactness pin, lowest first:

1. **Oracle** — ``batched.persistent_multigroup_rounds`` (the K-unrolled
   jnp program) against K sequential ``multigroup_fused_round`` calls,
   including a mid-wave freeze landing *between* rounds via
   ``enabled_rounds``.

2. **Kernel** — ``kernels.ops.persistent_cohort_rounds`` (one
   ``pallas_call``, grid ``(K, NB, B//BB)``) against both the oracle and
   K sequential ``cohort_fused_round`` dispatches, same chaos schedule.

3. **Dataplane** — ``pipeline_persistent`` against K ``pipeline_cohort``
   calls on all four backends (jnp/pallas x unsharded/sharded): outputs,
   register files and watermark mirrors all bit-identical; dispatch_count
   pins one launch per wave unsharded and the documented K-launch
   fallback sharded.

4. **Pump** — full ``PaxosContext`` runs with ``persistent_rounds`` and
   ``async_pump`` swept produce delivery logs bit-identical to the serial
   K=1 reference on every backend, including an async overlap schedule
   where the deliver callback submits fresh traffic mid-drain.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import batched
from repro.core.api import MultiGroupDataplane, PaxosContext, ShardedMultiGroupDataplane
from repro.core.plan import NOP_SENTINEL
from repro.core.types import NO_ROUND, CoordinatorState, PaxosConfig
from repro.kernels import ops as kops
from repro.launch.mesh import make_group_mesh

import jax
import jax.numpy as jnp

A = 3
QUORUM = 2


def _tree_equal(t1, t2):
    for l1, l2 in zip(jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2), strict=True):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def _wave_values(rng, k, g, b, v, fill=0.8):
    """Random wave values in the wire convention: inactive slots carry the
    NOP sentinel in word 0 (the kernel's only activity signal)."""
    vals = rng.integers(1, 1 << 20, size=(k, g, b, v)).astype(np.int32)
    active = rng.random((k, g, b)) < fill
    vals[~active, 0] = NOP_SENTINEL
    return vals, active


def _freeze_descriptor(k, g, b, marks, victim, at_round):
    """wni/wen for a wave where ``victim`` freezes between rounds
    ``at_round - 1`` and ``at_round``: its window stops walking and it
    sits out every later round (wni[k+1] = wni[k] + B * wen[k])."""
    wni = np.zeros((k, g), np.int32)
    wen = np.ones((k, g), np.int32)
    wen[at_round:, victim] = 0
    wni[0] = marks
    for r in range(1, k):
        wni[r] = wni[r - 1] + b * wen[r - 1]
    return wni, wen


# ---------------------------------------------------------------------------
# 1. Oracle: K-unrolled jnp program == K sequential fused rounds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("freeze_at", [None, 2])
def test_oracle_persistent_equals_sequential_rounds(freeze_at):
    g, n, b, v, k = 3, 256, 16, 4, 4
    rng = np.random.default_rng(7)
    vals, active = _wave_values(rng, k, g, b, v)
    alive = jnp.ones((g, A), bool)
    cstate, stack, lstate = batched.init_multigroup_state(g, A, n, v)

    victim = 1
    if freeze_at is None:
        enabled = None
    else:
        _, wen = _freeze_descriptor(k, g, b, [0] * g, victim, freeze_at)
        enabled = jnp.asarray(wen)

    pc, pstack, plstate, pfresh, pinst, pwin, pval = (
        batched.persistent_multigroup_rounds(
            cstate, stack, lstate, jnp.asarray(vals), jnp.asarray(active),
            alive, QUORUM, enabled_rounds=enabled,
        )
    )

    # the sequential reference: one fused round per k, the freeze applied
    # between rounds exactly as the dataplane masks a non-member cohort row
    sc, sstack, slstate = batched.init_multigroup_state(g, A, n, v)
    sf, si, sw, sv = [], [], [], []
    for r in range(k):
        if enabled is None:
            en = jnp.ones((g,), bool)
        else:
            en = enabled[r] != 0
        eff = CoordinatorState(
            next_inst=sc.next_inst, crnd=jnp.where(en, sc.crnd, NO_ROUND)
        )
        nc, sstack, slstate, fr, ii, wi, va = batched.multigroup_fused_round(
            eff, sstack, slstate, jnp.asarray(vals[r]),
            jnp.asarray(active[r]), alive, QUORUM,
        )
        sc = CoordinatorState(
            next_inst=jnp.where(en, nc.next_inst, sc.next_inst), crnd=sc.crnd
        )
        sf.append(fr), si.append(ii), sw.append(wi), sv.append(va)

    _tree_equal((pc, pstack, plstate), (sc, sstack, slstate))
    _tree_equal(
        (pfresh, pinst, pwin, pval),
        (jnp.stack(sf), jnp.stack(si), jnp.stack(sw), jnp.stack(sv)),
    )
    if freeze_at is not None:
        # the frozen group's watermark stopped at the freeze boundary
        assert int(pc.next_inst[victim]) == freeze_at * b
        assert not np.asarray(pfresh)[freeze_at:, victim].any()


# ---------------------------------------------------------------------------
# 2. Kernel: one pallas_call == oracle == K sequential cohort dispatches,
#    with a chaos freeze landing between rounds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("freeze_at", [None, 1])
def test_kernel_persistent_wave_chaos_parity(freeze_at):
    g, n, b, v, k = 3, 256, 16, 4, 4
    rng = np.random.default_rng(11)
    vals, active = _wave_values(rng, k, g, b, v)
    alive_i = jnp.ones((g, A), jnp.int32)
    crnd = jnp.zeros((g,), jnp.int32)
    _, stack, lstate = batched.init_multigroup_state(g, A, n, v)

    victim, marks = 2, [0] * g
    wni, wen = _freeze_descriptor(
        k, g, b, marks, victim, k if freeze_at is None else freeze_at
    )
    gsel = np.arange(g, dtype=np.int32)  # gb = 1: every group its own block

    kstack, klstate, kfresh, kwin, kval = kops.persistent_cohort_rounds(
        stack, lstate, jnp.asarray(gsel), jnp.asarray(wni), jnp.asarray(wen),
        crnd, alive_i, QUORUM, jnp.asarray(vals),
        group_block=1, block_b=b,
    )

    # oracle mirror of the same wave descriptor
    cstate, ostack, olstate = batched.init_multigroup_state(g, A, n, v)
    _, ostack, olstate, ofresh, _oi, owin, oval = (
        batched.persistent_multigroup_rounds(
            cstate, ostack, olstate, jnp.asarray(vals), jnp.asarray(active),
            jnp.ones((g, A), bool), QUORUM,
            enabled_rounds=jnp.asarray(wen),
        )
    )
    _tree_equal((kstack, klstate), (ostack, olstate))
    _tree_equal((kfresh, kwin, kval), (ofresh != 0, owin, oval))

    # sequential kernel reference: K cohort dispatches, the freeze applied
    # between dispatches (enabled mask + a watermark that stops walking)
    _, sstack, slstate = batched.init_multigroup_state(g, A, n, v)
    sf, sw, sv = [], [], []
    for r in range(k):
        sstack, slstate, fr, wi, va = kops.cohort_fused_round(
            sstack, slstate, jnp.asarray(gsel), jnp.asarray(wni[r]), crnd,
            alive_i, QUORUM, jnp.asarray(vals[r]), jnp.asarray(wen[r]),
            group_block=1,
        )
        sf.append(fr), sw.append(wi), sv.append(va)
    _tree_equal((kstack, klstate), (sstack, slstate))
    _tree_equal(
        (kfresh, kwin, kval),
        (jnp.stack(sf), jnp.stack(sw), jnp.stack(sv)),
    )


# ---------------------------------------------------------------------------
# 3. Dataplane: pipeline_persistent == K x pipeline_cohort, four backends
# ---------------------------------------------------------------------------
def _mk_plane(use_kernels, sharded, cfg):
    if sharded:
        return ShardedMultiGroupDataplane(
            cfg, mesh=make_group_mesh(), use_kernels=use_kernels
        )
    return MultiGroupDataplane(cfg, use_kernels=use_kernels)


@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("sharded", [False, True])
def test_pipeline_persistent_equals_k_cohorts(use_kernels, sharded):
    # a wave that fills the whole ring (K * BE = N) without lapping it
    _check_persistent_equals_k_cohorts(use_kernels, sharded, n=256, be=128, k=2)


@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("sharded", [False, True])
def test_block_aligned_persistent_equals_k_cohorts(use_kernels, sharded):
    # block-aligned rounds: the kernel path runs the persistent wave kernel
    _check_persistent_equals_k_cohorts(use_kernels, sharded, n=512, be=128, k=3)


def _check_persistent_equals_k_cohorts(use_kernels, sharded, n, be, k):
    g, v = 2, 4
    cfg = PaxosConfig(
        n_acceptors=A, n_instances=n, value_words=v, batch=be, n_groups=g
    )
    rng = np.random.default_rng(23)
    vals, active = _wave_values(rng, k, g, be, v)
    gids = (0, 1)

    hw_p = _mk_plane(use_kernels, sharded, cfg)
    fresh_p, inst_p, val_p = hw_p.pipeline_persistent(gids, vals, active)
    assert fresh_p.shape == (k, g, be)

    hw_s = _mk_plane(use_kernels, sharded, cfg)
    outs = [hw_s.pipeline_cohort(gids, vals[r], active[r]) for r in range(k)]

    np.testing.assert_array_equal(fresh_p, np.stack([o[0] for o in outs]))
    np.testing.assert_array_equal(inst_p, np.stack([o[1] for o in outs]))
    np.testing.assert_array_equal(val_p, np.stack([o[2] for o in outs]))
    _tree_equal(
        (hw_p.stack, hw_p.lstate, hw_p.cstate),
        (hw_s.stack, hw_s.lstate, hw_s.cstate),
    )
    assert hw_p.next_inst_host == hw_s.next_inst_host == [k * be] * g
    # one device launch per wave — except the documented sharded K=1
    # fallback, which dispatches per round
    assert hw_p.dispatch_count == (k if sharded else 1)
    assert hw_p.persistent_dispatch_count == (0 if sharded else 1)
    assert hw_s.dispatch_count == k


def test_pipeline_persistent_rejects_ring_lap():
    cfg = PaxosConfig(
        n_acceptors=A, n_instances=64, value_words=4, batch=32, n_groups=1
    )
    hw = MultiGroupDataplane(cfg)
    vals = np.zeros((3, 1, 32, 4), np.int32)
    vals[..., 0] = NOP_SENTINEL
    act = np.zeros((3, 1, 32), bool)
    with pytest.raises(ValueError, match="lap"):
        hw.pipeline_persistent((0,), vals, act)


def test_pipeline_persistent_kernel_refuses_off_block_wave():
    # rounds of 16 slots share 128-slot ring blocks: the persistent kernel
    # cannot run them, and the dataplane refuses before any state moves
    cfg = PaxosConfig(
        n_acceptors=A, n_instances=128, value_words=4, batch=16, n_groups=2
    )
    hw = MultiGroupDataplane(cfg, use_kernels=True)
    vals, act = _wave_values(np.random.default_rng(5), 3, 2, 16, 4)
    with pytest.raises(ValueError, match="ring-block"):
        hw.pipeline_persistent((0, 1), vals, act)
    assert hw.dispatch_count == 0 and hw.next_inst_host == [0, 0]


# ---------------------------------------------------------------------------
# 4. Pump: persistent waves + async double-buffering vs the serial reference
# ---------------------------------------------------------------------------
def _run_ctx(use_kernels, mesh, pr, async_pump, n_extra=0):
    cfg = PaxosConfig(
        n_acceptors=A, n_instances=1 << 10, value_words=4, batch=128,
        n_groups=2, persistent_rounds=pr, async_pump=async_pump,
    )
    ctx = PaxosContext(cfg, use_kernels=use_kernels, mesh=mesh)
    # group 0 deep enough for multi-round waves, group 1 a ragged tail —
    # the wave loop mints mixed cohorts and a trailing sub-batch burst
    for i in range(520):
        ctx.submit(f"a{i:04d}".encode(), group=0)
    for i in range(180):
        ctx.submit(f"b{i:04d}".encode(), group=1)
    ctx.run_until_quiescent()
    for i in range(n_extra):
        ctx.submit(f"x{i:04d}".encode(), group=i % 2)
    ctx.run_until_quiescent()
    return ctx


@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("sharded", [False, True])
def test_pump_persistent_waves_bit_identical_four_backends(use_kernels, sharded):
    ref = _run_ctx(False, None, pr=1, async_pump=False)
    mesh = make_group_mesh() if sharded else None
    for pr in (4, 1):
        for ap in (True, False):
            ctx = _run_ctx(use_kernels, mesh, pr=pr, async_pump=ap)
            assert ctx.group_log == ref.group_log, (use_kernels, sharded, pr, ap)
            assert ctx.quiescent()


def test_pump_dispatch_count_one_launch_per_wave():
    # 520 submits / batch 128 -> one K=4 persistent wave (512) + one
    # 8-row tail burst = 2 launches; the K=1 pump needs 5
    ctx = _run_ctx(True, None, pr=4, async_pump=True)
    assert ctx.hw.dispatch_count == 2 + 2  # group-1 traffic adds 2 bursts
    assert ctx.planner.stats["persistent_waves"] == 1
    assert ctx.hw.persistent_dispatch_count == 1
    ref = _run_ctx(True, None, pr=1, async_pump=False)
    assert ref.planner.stats["persistent_waves"] == 0
    assert ctx.hw.dispatch_count < ref.hw.dispatch_count
    # sharded: the planner itself clamps wave depth to K=1 (DESIGN.md §13),
    # so no persistent wave is ever minted — the telemetry no longer
    # over-counts waves the dispatch layer would have unrolled anyway
    sh = _run_ctx(True, make_group_mesh(), pr=4, async_pump=True)
    assert sh.planner.stats["persistent_waves"] == 0
    assert sh.hw.dispatch_count == ref.hw.dispatch_count
    assert sh.group_log == ctx.group_log == ref.group_log


def test_async_pump_overlap_with_midstream_submissions():
    """The overlap pin: a deliver callback that submits fresh traffic while
    a wave is still in flight must not fork delivery between the
    double-buffered and the serial pump."""
    logs = {}
    for ap in (True, False):
        cfg = PaxosConfig(
            n_acceptors=A, n_instances=1 << 10, value_words=4, batch=128,
            n_groups=2, persistent_rounds=4, async_pump=ap,
        )
        fired = []

        def follow_up(payload, size, inst):
            if payload == b"a0000" and not fired:
                fired.append(inst)
                for j in range(160):
                    ctx.submit(f"f{j:04d}".encode(), group=1)

        ctx = PaxosContext(cfg, deliver=follow_up)
        for i in range(384):
            ctx.submit(f"a{i:04d}".encode(), group=0)
        ctx.run_until_quiescent()
        assert ctx.quiescent()
        assert fired, "overlap callback never fired"
        logs[ap] = ctx.group_log
    assert logs[True] == logs[False]
    assert len(logs[True][1]) == 160  # the mid-drain follow-ups all landed
