"""Cohort dispatch planner (core.plan, DESIGN.md §8).

Three contracts under test:

1. **Planner policy units** — pow2 burst quantization, cohort tiering
   (one dispatch per distinct burst), per-cohort fold widths generalizing
   the old ``group_block ∈ {G, 1}`` cliff, and group-axis compaction for
   the kernel path.

2. **Bounded burst-shape vocabulary** — a heavily skewed 1000-submit run
   must mint only pow2 burst shapes in ``[MIN_BURST, batch]``, on the
   fused and the staged (software-coordinated) paths alike, so the jit
   cache cannot churn one compiled program per load level.

3. **Lockstep realignment** — after divergent per-group failovers the
   planner burns the stragglers forward to a common block boundary within
   ``realign_after`` sweeps, the full-width fold re-engages
   (``group_block == G``), and the burned NOP instances never surface in
   ``delivered()``.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import PaxosConfig, PaxosContext
from repro.core import plan as plan_mod
from repro.core.plan import (
    MIN_BURST,
    NO_ROUND,
    DispatchPlanner,
    cohort_blocks,
    fold_width_full,
    quantize_burst,
)
from repro.serve.engine import ConsensusService


# ---------------------------------------------------------------------------
# Policy units
# ---------------------------------------------------------------------------
def test_quantize_burst_pow2_floor_and_cap():
    assert quantize_burst(0, 128) == MIN_BURST
    assert quantize_burst(1, 128) == MIN_BURST
    assert quantize_burst(8, 128) == 8
    assert quantize_burst(9, 128) == 16
    assert quantize_burst(100, 128) == 128
    assert quantize_burst(1000, 128) == 128       # capped at batch
    assert quantize_burst(3, 4) == 4              # cap below the floor


def test_fold_width_full_generalizes_the_binary_cliff():
    # full lockstep: the whole capacity folds
    assert fold_width_full([0, 1, 2, 3], [8, 8, 8, 8], 4) == 4
    # two lockstep halves: the historical plan fell to 1; now width 4
    marks = [0, 0, 0, 0, 8, 8, 8, 8]
    assert fold_width_full(list(range(8)), marks, 8) == 4
    # fully divergent: width 1
    assert fold_width_full([0, 1], [0, 8], 2) == 1
    # divergence only among NON-members never constrains the fold
    assert fold_width_full([1, 2, 3], [99, 8, 8, 8], 4) == 4
    # empty member set: unconstrained
    assert fold_width_full([], [0, 1, 2, 3], 4) == 4


@pytest.mark.parametrize(
    "n,bases,b,want",
    [
        (512, [0], 128, 1),          # aligned: exactly the window's block
        (512, [8], 128, 2),          # off the boundary: one block more
        (512, [120, 0], 8, 1),       # a short burst inside one block
        (512, [124], 8, 2),          # ...or straddling two
        (65536, [3], 8192, 65),
        (64, [56], 8, 1),            # a short ring is one block
        (64, [60], 8, None),         # wraps onto its own block: jnp
    ],
)
def test_window_blocks_cover_any_window(n, bases, b, want):
    assert plan_mod.window_blocks(n, bases, b) == want
    assert plan_mod.blocks_aligned(n, bases, b) == (
        want == 1 and b % plan_mod.ring_block(n) == 0
        and all(x % plan_mod.ring_block(n) == 0 for x in bases)
    )


def test_fold_cap_bounds_a_grid_step():
    # a grid step holds at most MAX_FOLD_LANES group-slots
    assert plan_mod.fold_cap(256, 65536, 3, 16) == plan_mod.MAX_FOLD_LANES // 128
    assert plan_mod.fold_cap(256, 65536, 5, 16) == plan_mod.MAX_FOLD_LANES // 128
    assert plan_mod.fold_cap(8, 65536, 3, 16) == 8
    assert plan_mod.fold_cap(96, 65536, 3, 16) == 48      # a divisor of G
    assert plan_mod.fold_cap(4, 64, 3, 16) == 4
    # more acceptors or value words than the compiled shape: fewer groups
    assert plan_mod.fold_cap(64, 65536, 7, 16) == 32
    assert plan_mod.fold_cap(64, 65536, 3, 64) == 16


def test_cohort_blocks_compacts_the_group_axis():
    marks = [0] * 8
    # a single hot group: one width-1 block, not a full-width sweep
    gb, blocks = cohort_blocks([2], marks, 8)
    assert (gb, blocks) == (1, [2])
    # 7-of-8 cold cohort: one folded full-width block beats 7 single blocks
    gb, blocks = cohort_blocks(list(range(1, 8)), marks, 8)
    assert (gb, blocks) == (8, [0])
    # two divergent lockstep halves fold block-wise at width 4
    marks = [0, 0, 0, 0, 8, 8, 8, 8]
    gb, blocks = cohort_blocks(list(range(8)), marks, 8)
    assert (gb, blocks) == (4, [0, 1])
    # divergent neighbours cannot share a block
    gb, blocks = cohort_blocks([0, 1], [0, 8], 2)
    assert (gb, blocks) == (1, [0, 1])


def test_plan_round_tiers_hot_to_cold():
    p = DispatchPlanner(batch=128, n_instances=4096)
    rp = p.plan_round(
        loads=[128, 2, 0, 7, 128, 1],
        marks=[0] * 6,
        live=[True] * 6,
        crnd=[0] * 6,
    )
    # one dispatch per distinct quantized burst, hot first
    assert [c.burst for c in rp.cohorts] == [128, 8]
    assert rp.cohorts[0].gids == (0, 4)
    assert rp.cohorts[1].gids == (1, 3, 5)
    assert rp.enabled == (True, True, False, True, True, True)
    assert not rp.full_fold                      # two tiers
    assert rp.fragmentation == 1                 # but one watermark class


def test_plan_round_masks_frozen_and_vacant():
    p = DispatchPlanner(batch=32, n_instances=512)
    rp = p.plan_round(
        loads=[4, 4, 4, 4],
        marks=[0, 0, 0, 0],
        live=[True, False, True, True],          # group 1 vacant
        crnd=[0, 0, NO_ROUND, 0],                # group 2 frozen
    )
    assert rp.enabled == (True, False, False, True)
    assert rp.cohorts == (plan_mod.Cohort(gids=(0, 3), burst=8),)
    assert rp.full_fold


def test_realignment_sweep_triggers_after_k_fragmented_rounds():
    p = DispatchPlanner(batch=128, n_instances=4096, realign_after=3)
    marks = [128, 256, 128, 128]
    for _ in range(2):
        rp = p.plan_round([4] * 4, marks, [True] * 4, [0] * 4)
        assert rp.realign == ()                  # below the threshold
        assert rp.fragmentation == 2
    rp = p.plan_round([4] * 4, marks, [True] * 4, [0] * 4)
    # third consecutive fragmented round: burn to the common block boundary
    # (gid 1 already sits on it and is not burned)
    burned = dict(rp.realign)
    assert set(burned) == {0, 2, 3}
    assert all(t == 256 for t in burned.values())
    assert rp.fragmentation == 1
    assert rp.full_fold
    assert p.stats["realignments"] == 1
    # the counter reset: the next fragmented round starts a fresh window
    rp = p.plan_round([4] * 4, [0, 64, 0, 0], [True] * 4, [0] * 4)
    assert rp.realign == ()


def test_realignment_fires_on_lockstep_but_misaligned_watermarks():
    """Fragmentation is not only fold divergence: enabled groups in
    lockstep at a watermark OFF the full-batch block boundary (the residue
    a right-sized sub-batch burst leaves) can never run the block-aligned
    kernel window — the sweep must burn them forward too, and it must fire
    identically on every engine (the trigger reads host scalars only)."""
    p = DispatchPlanner(batch=32, n_instances=512, realign_after=2)
    marks = [8, 8, 8, 8]                         # one class, 8 % 32 != 0
    rp = p.plan_round([4] * 4, marks, [True] * 4, [0] * 4)
    assert rp.realign == ()
    rp = p.plan_round([4] * 4, marks, [True] * 4, [0] * 4)
    burned = dict(rp.realign)
    assert set(burned) == {0, 1, 2, 3}
    assert all(t == 32 for t in burned.values())  # next 32-block boundary
    assert rp.full_fold
    # aligned lockstep marks are NOT fragmented: the counter resets
    rp = p.plan_round([4] * 4, [32] * 4, [True] * 4, [0] * 4)
    assert rp.realign == () and p._fragmented_rounds == 0


def test_realignment_disabled_by_default():
    p = DispatchPlanner(batch=128, n_instances=4096)
    for _ in range(50):
        rp = p.plan_round([4] * 4, [0, 64, 0, 0], [True] * 4, [0] * 4)
        assert rp.realign == ()
    assert p.stats["realignments"] == 0


# ---------------------------------------------------------------------------
# Bounded burst-shape vocabulary (jit-cache churn guard)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("use_kernels", [False, True])
def test_skewed_1000_submit_run_mints_bounded_burst_shapes(use_kernels):
    """1000 submits with per-group loads swept across every level — plus a
    stretch under a software coordinator (the staged path) — must resolve
    to pow2 bursts in [MIN_BURST, batch] only: at most
    log2(batch/MIN_BURST)+1 distinct wire shapes ever reach a dispatch."""
    cfg = PaxosConfig(
        n_acceptors=3, n_instances=2048, batch=64, n_groups=4
    )
    ctx = PaxosContext(cfg, use_kernels=use_kernels)
    rng = np.random.default_rng(0)
    submitted = 0
    wave = 0
    while submitted < 1000:
        if wave == 6:
            ctx.fail_coordinator(group=1)        # staged path for group 1
        if wave == 12:
            ctx.restore_hardware_coordinator(group=1)
        for gid in range(4):
            k = int(rng.integers(0, cfg.batch + 1)) if gid else cfg.batch
            for j in range(k):
                ctx.submit(f"w{wave}g{gid}j{j}".encode(), group=gid)
                submitted += 1
        ctx.run_until_quiescent()
        wave += 1
    assert ctx.stats["delivered"] == submitted
    shapes = ctx.planner.stats["burst_shapes"]
    legal = {8, 16, 32, 64}                      # pow2 in [MIN_BURST, batch]
    assert shapes <= legal, shapes
    assert len(shapes) <= 4


# ---------------------------------------------------------------------------
# Lockstep realignment, end to end
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("use_kernels", [False, True])
def test_realignment_restores_full_width_fold_after_failover(use_kernels):
    """Scripted divergent failover: after restore, the victim's watermark
    sits off the others' class and the plan fragments; within
    ``realign_after`` loaded sweeps the planner burns the stragglers
    forward, the full-width fold (group_block == G) re-engages, and every
    submitted payload — and nothing else — is delivered."""
    g = 4
    cfg = PaxosConfig(
        n_acceptors=3, n_instances=512, batch=32, n_groups=g,
        realign_after=2,
    )
    ctx = PaxosContext(cfg, use_kernels=use_kernels)
    sent = [[] for _ in range(g)]

    def wave(tag, extra=0):
        for gid in range(g):
            for j in range(1 + (extra if gid == 1 else 0)):
                p = f"{tag}g{gid}j{j}".encode()
                sent[gid].append(p)
                ctx.submit(p, group=gid)
        ctx.run_until_quiescent()

    wave("w0")
    ctx.fail_coordinator(group=1)
    # heavier load on the victim while software-coordinated: its burst
    # right-sizes to 16 where the others advance by 8, so the watermarks
    # genuinely diverge on every backend
    wave("w1", extra=8)
    wave("w2")
    ctx.restore_hardware_coordinator(group=1)
    # the victim's restore-realigned watermark diverges from the others'
    assert len(set(ctx.hw.next_inst_host)) > 1
    for k in range(cfg.realign_after + 1):
        wave(f"r{k}")
    # the sweep fired, the service is back in lockstep, and the dispatch
    # folds the full width again
    assert ctx.planner.stats["realignments"] >= 1
    assert len(set(ctx.hw.next_inst_host)) == 1
    assert ctx.planner.last_plan.full_fold
    assert ctx.hw.last_gb == g
    assert ctx.hw._plan_round(cfg.batch, None)[2] == g
    wave("post")
    # burned instances are NOP holes: never proposed, never delivered —
    # each group's log is exactly its submissions, in order
    for gid in range(g):
        assert [p for _i, p in ctx.group_log[gid]] == sent[gid], gid
    assert not ctx._pending


def test_realignment_burns_never_surface_in_service_delivered():
    """The serving-tier view of the same sweep: sessions routed through
    ``ConsensusService.delivered`` observe exactly their own ops, in
    order, across a failover + realignment — burned instances are holes
    in the instance space, not entries in any session's log."""
    cfg = PaxosConfig(
        n_acceptors=3, n_instances=512, batch=32, n_groups=4,
        realign_after=2,
    )
    svc = ConsensusService(PaxosContext(cfg, use_kernels=True))
    sessions = [f"user-{i}" for i in range(12)]
    victim = svc.group_of(sessions[0])

    def wave(tag):
        for s in sessions:
            svc.submit(s, f"{s}:{tag}".encode())
        svc.run_until_quiescent()

    wave("op0")
    svc.ctx.fail_coordinator(group=victim)
    wave("op1")
    svc.ctx.restore_hardware_coordinator(group=victim)
    for k in range(4):
        wave(f"op{2 + k}")
    report = svc.plan_report()
    assert report["realignments"] >= 1
    assert report["service_loads"] == svc.group_loads()
    for s in sessions:
        mine = [
            p for _i, p in svc.delivered(s)
            if p.startswith(f"{s}:".encode())
        ]
        assert mine == [f"{s}:op{k}".encode() for k in range(6)]


def test_burn_forward_is_monotone_and_plan_is_backend_agnostic():
    cfg = PaxosConfig(n_acceptors=3, n_instances=256, batch=16, n_groups=2)
    ctx = PaxosContext(cfg)
    ctx.hw.burn_forward(1, 32)
    assert ctx.hw.next_inst_host == [0, 32]
    assert int(np.asarray(ctx.hw.cstate.next_inst)[1]) == 32
    with pytest.raises(ValueError):
        ctx.hw.burn_forward(1, 16)
    # the group still serves from the burned watermark
    ctx.submit(b"x", group=1)
    ctx.run_until_quiescent()
    assert [(i, p) for i, p in ctx.group_log[1]] == [(32, b"x")]


# ---------------------------------------------------------------------------
# Dispatch-path hardening (DESIGN.md §11 ride-alongs)
# ---------------------------------------------------------------------------
def test_pack_rows_oversized_chunk_fails_up_front():
    """An oversized chunk must fail before any wire array is built — the
    historical loop raised a bare IndexError after partially writing the
    burst — and the error must name both the chunk length and the burst."""
    rows = [np.full((4,), 7, np.int32) for _ in range(9)]
    with pytest.raises(ValueError) as ei:
        plan_mod.pack_rows(rows, 8, 4)
    assert "9" in str(ei.value) and "8" in str(ei.value)
    # the boundary case still packs
    vals, active = plan_mod.pack_rows(rows[:8], 8, 4)
    assert active.all() and (vals == 7).all()


def test_report_snapshots_service_loads_not_aliases():
    """A report is an observation, not a window onto live planner state:
    mutating a returned report must not perturb the planner, and later
    load observations must not rewrite already-returned reports."""
    p = DispatchPlanner(batch=32, n_instances=512)
    p.observe_service_loads([3, 1, 4])
    r1 = p.report()
    r1["service_loads"].append(99)
    r1["burst_shapes"].append(77)
    assert p.stats["service_loads"] == [3, 1, 4]
    assert p.report()["service_loads"] == [3, 1, 4]
    r2 = p.report()
    p.observe_service_loads([0, 0, 0])
    assert r2["service_loads"] == [3, 1, 4]
    assert p.report()["service_loads"] == [0, 0, 0]


def test_wave_depth_policy_full_batch_and_covered_queues_only():
    """The planner mints K > 1 only for full-batch cohorts whose every
    member has K full chunks queued at a ring-block-aligned watermark,
    clamped by the policy knob and the ring (DESIGN.md §11)."""
    p = DispatchPlanner(batch=128, n_instances=512, persistent_rounds=8)
    rp = p.plan_round(
        loads=[128, 128], marks=[0, 0], live=[True] * 2, crnd=[0, 0],
        pending=[640, 384],
    )
    # min(640, 384) // 128 = 3 full chunks each; ring cap 512 // 128 = 4
    assert rp.cohorts == (plan_mod.Cohort(gids=(0, 1), burst=128, rounds=3),)
    assert p.stats["persistent_waves"] == 1
    # a watermark off the 128-slot ring block never goes persistent (the
    # kernel's rounds must share no block), on every engine alike
    rp = p.plan_round(
        loads=[128, 128], marks=[128, 136], live=[True] * 2, crnd=[0, 0],
        pending=[640, 384],
    )
    assert all(c.rounds == 1 for c in rp.cohorts)
    assert p.stats["persistent_waves"] == 1
    # a sub-batch burst never goes persistent (numbering would fork)
    rp = p.plan_round(
        loads=[8, 8], marks=[0, 0], live=[True] * 2, crnd=[0, 0],
        pending=[64, 64],
    )
    assert all(c.rounds == 1 for c in rp.cohorts)
    # no pending telemetry -> classic single-round planning
    rp = p.plan_round(
        loads=[128, 128], marks=[0, 0], live=[True] * 2, crnd=[0, 0]
    )
    assert all(c.rounds == 1 for c in rp.cohorts)
    # the knob off switches the feature off wholesale
    p1 = DispatchPlanner(batch=128, n_instances=512, persistent_rounds=1)
    rp = p1.plan_round(
        loads=[128], marks=[0], live=[True], crnd=[0], pending=[1280],
    )
    assert rp.cohorts[0].rounds == 1
    assert p1.stats["persistent_waves"] == 0

# -- load-weighted placement (DESIGN.md §13) ---------------------------------

def test_placement_identity_and_validation():
    pm = plan_mod.PlacementMap.identity(8, 4)
    assert pm.identity_map()
    assert pm.n_groups == 8 and pm.n_shards == 2
    assert [pm.shard_of(g) for g in range(8)] == [0] * 4 + [1] * 4
    assert [pm.row_of(g) for g in range(8)] == [0, 1, 2, 3] * 2
    assert pm.group_of == tuple(range(8))
    with pytest.raises(ValueError):
        plan_mod.PlacementMap((0, 0, 1, 3), 2)   # not a permutation
    with pytest.raises(ValueError):
        plan_mod.PlacementMap((0, 1, 2), 2)      # G not divisible by Gl


def test_weighted_placement_is_ragged_and_load_balanced():
    """LPT greedy: one hot tenant claims a shard while the cold majority
    packs elsewhere — a ragged, non-contiguous assignment, not equal
    contiguous slabs."""
    pm = plan_mod.PlacementMap.weighted([100, 1, 1, 1, 1, 1, 1, 1], 2, 4)
    shards = [pm.shard_of(g) for g in range(8)]
    # the hot group sits alone-ish: its shard hosts the LIGHT tail only
    # after the other shard fills to capacity
    hot = shards[0]
    cold_sum = sum(1 for g in range(1, 8) if shards[g] != hot)
    assert cold_sum == 4  # cold shard filled to Gl before spill-back
    # the assignment is non-contiguous: the hot shard's co-tenants are not
    # a prefix/suffix run of group ids
    mates = sorted(g for g in range(1, 8) if shards[g] == hot)
    assert mates == [5, 6, 7]
    # still a permutation; every backend resolves the same map
    assert sorted(pm.slot_of) == list(range(8))
    assert pm == plan_mod.PlacementMap.weighted(
        [100, 1, 1, 1, 1, 1, 1, 1], 2, 4
    )


def test_weighted_placement_stable_under_equal_loads():
    """Equal loads degrade to round-robin gid i -> shard i % n_shards, so
    an all-idle service keeps the identity-like layout deterministically."""
    for loads in ([0] * 8, [5] * 8):
        pm = plan_mod.PlacementMap.weighted(loads, 2, 4)
        assert [pm.shard_of(g) for g in range(8)] == [g % 2 for g in range(8)]
        # repeated planning is a fixed point
        assert pm == plan_mod.PlacementMap.weighted(loads, 2, 4)


def test_placement_swap_is_migrations_only_mutation():
    pm = plan_mod.PlacementMap.identity(4, 2)
    moved = pm.swapped(0, 3)
    assert moved.slot_of == (3, 1, 2, 0)
    assert moved.shard_of(0) == 1 and moved.shard_of(3) == 0
    # swap back restores identity; a swap never breaks the permutation
    assert moved.swapped(0, 3) == pm
    assert sorted(moved.group_of) == list(range(4))
    with pytest.raises(ValueError):
        plan_mod.PlacementMap.weighted([1, 2, 3], 2, 2)  # wrong cardinality


def test_sharded_planner_clamps_wave_depth_to_one():
    """Pin: a sharded planner never mints K > 1 — the wave would unroll to
    K dispatches anyway, and ``persistent_waves`` must count only waves
    that actually ran device-persistent (DESIGN.md §11)."""
    p = DispatchPlanner(
        batch=128, n_instances=512, persistent_rounds=8, sharded=True
    )
    rp = p.plan_round(
        loads=[128, 128], marks=[0, 0], live=[True] * 2, crnd=[0, 0],
        pending=[640, 384],
    )
    # the identical inputs mint rounds=3 on the unsharded planner (above)
    assert rp.cohorts == (plan_mod.Cohort(gids=(0, 1), burst=128, rounds=1),)
    assert p.stats["persistent_waves"] == 0


def test_sharded_program_vocabulary_is_closed_and_small():
    """The four-chip deployment's 1,024 groups of the paper's shape: every
    program its sharded dispatch may run is one of 25, listed from the
    configuration and the shard count alone."""
    cfg = PaxosConfig(n_acceptors=3, n_instances=65536, value_words=16,
                      batch=128, n_groups=1024)
    progs = plan_mod.sharded_programs(cfg, 4, kernels=True)
    assert len(progs) == len(set(progs)) == 25
    assert plan_mod.burst_sizes(128) == [8, 16, 32, 64, 128]
    assert {p.width for p in progs if p.kind == "packed"} == {1, 8, 64}
    assert {p.width for p in progs if p.kind == "full"} == {1, 64}
    assert {p.window_blocks for p in progs} == {2}
    # the jnp engine: one window-less program per lane count and burst
    jnp_progs = plan_mod.sharded_programs(cfg, 4, kernels=False)
    assert {p.window_blocks for p in jnp_progs} == {None}
    assert {p.width for p in jnp_progs if p.kind == "full"} == {1}
    # a ring a window can wrap lists the jnp fallback beside the kernels
    small = PaxosConfig(n_acceptors=3, n_instances=64, batch=8, n_groups=2)
    assert {p.window_blocks for p in plan_mod.sharded_programs(small, 1, True)} == {1, None}


@pytest.mark.parametrize("residency,gl,want", [
    (1, 256, 1), (2, 256, 8), (8, 256, 8), (9, 256, 64), (64, 256, 64),
    (65, 256, None), (1, 1, None), (3, 16, 8), (9, 16, None),
])
def test_packed_lanes_step_by_lane_step(residency, gl, want):
    assert plan_mod.packed_lanes(residency, gl) == want


def test_full_fold_is_the_cap_or_one():
    marks = [0] * 8 + [16] * 8
    assert plan_mod.full_fold(range(16), marks, 8) == 8
    assert plan_mod.full_fold(range(16), marks, 16) == 1
    # fold_width_full would find 4 here; the vocabulary runs it unfolded
    marks = [0] * 4 + [16] * 4
    assert fold_width_full(range(8), marks, 8) == 4
    assert plan_mod.full_fold(range(8), marks, 8) == 1


@pytest.mark.parametrize("n,b,want", [(65536, 8, 2), (65536, 128, 2), (128, 16, 1),
                                      (64, 8, 1), (1024, 256, 3)])
def test_sharded_window_blocks_follow_the_burst(n, b, want):
    assert plan_mod.sharded_window_blocks(n, b) == want
    bb = plan_mod.ring_block(n)
    # enough for every window that fits the ring, from any offset
    for base in range(0, bb, 8):
        got = plan_mod.window_blocks(n, [base], b)
        assert got is None or got <= want
