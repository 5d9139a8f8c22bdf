"""Multi-group consensus as a service: context-level parity and routing.

The contract under test (DESIGN.md §5): a ``PaxosContext`` over G
device-resident groups behaves exactly like G *independent* single-group
contexts — same per-group delivery logs, same device register files — while
actually advancing all groups through ONE fused dispatch per burst.  That
must hold through per-group acceptor death and a coordinator failover in one
group (which may not perturb any other group), on both the jnp oracle path
and the Pallas kernel path.  ``ConsensusService`` adds the serving tier:
deterministic session -> group hash routing.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core import MultiGroupDataplane, PaxosConfig, PaxosContext
from repro.serve.engine import ConsensusService, session_group

G = 4
CFG_MG = PaxosConfig(n_acceptors=3, n_instances=512, batch=16, n_groups=G)
CFG_1 = PaxosConfig(n_acceptors=3, n_instances=512, batch=16)


def _group_state(hw, gid: int):
    """Host copies of one group's acceptor + learner device state."""
    src = (hw.stack, hw.lstate)
    if isinstance(hw, MultiGroupDataplane):
        src = jax.tree_util.tree_map(lambda x: x[gid], src)
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(src)]


def _run_schedule(ctx, groups, waves, use_groups: bool):
    """Submit ``waves`` rounds of one payload per group, pumping each wave."""
    for w in range(waves):
        for gid in groups:
            payload = f"w{w}g{gid}".encode()
            if use_groups:
                ctx.submit(payload, group=gid)
            else:
                ctx.submit(payload)
        ctx.run_until_quiescent()


@pytest.mark.parametrize("use_kernels", [False, True])
def test_groups_match_independent_contexts(use_kernels):
    """G fused groups == G independent single-group contexts, bit for bit,
    including a dead acceptor in one group."""
    mg = PaxosContext(CFG_MG, use_kernels=use_kernels)
    singles = [
        PaxosContext(CFG_1, use_kernels=use_kernels, fused=True)
        for _ in range(G)
    ]
    mg.hw.kill_acceptor(2, 1)       # group 2 loses acceptor 1...
    singles[2].hw.kill_acceptor(1)  # ...and so does its independent twin

    _run_schedule(mg, range(G), waves=3, use_groups=True)
    for gid, ctx in enumerate(singles):
        _run_schedule(ctx, [gid], waves=3, use_groups=False)

    for gid, ctx in enumerate(singles):
        assert mg.group_log[gid] == ctx.delivered_log, gid
        for a, b in zip(_group_state(mg.hw, gid), _group_state(ctx.hw, gid), strict=True):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("use_kernels", [False, True])
def test_group_failover_does_not_perturb_others(use_kernels):
    """Coordinator failover in one group: that group fails over to software
    sequencing and back, while every other group's delivery log and device
    registers stay bit-identical to independent contexts that never saw a
    failover."""
    victim = 1
    mg = PaxosContext(CFG_MG, use_kernels=use_kernels)
    singles = [
        PaxosContext(CFG_1, use_kernels=use_kernels, fused=True)
        for _ in range(G)
    ]

    _run_schedule(mg, range(G), waves=2, use_groups=True)
    for gid, ctx in enumerate(singles):
        _run_schedule(ctx, [gid], waves=2, use_groups=False)

    mg.fail_coordinator(group=victim)
    singles[victim].fail_coordinator()

    _run_schedule(mg, range(G), waves=2, use_groups=True)
    for gid, ctx in enumerate(singles):
        _run_schedule(ctx, [gid], waves=2, use_groups=False)

    mg.restore_hardware_coordinator(group=victim)
    singles[victim].restore_hardware_coordinator()
    planes = [mg.hw] + [ctx.hw for ctx in singles]
    jnp_before = [hw.jnp_dispatch_count for hw in planes]

    _run_schedule(mg, range(G), waves=2, use_groups=True)
    for gid, ctx in enumerate(singles):
        _run_schedule(ctx, [gid], waves=2, use_groups=False)

    # the restored watermark is wherever the software coordinator left it;
    # hardware-sequenced rounds run it on the kernel path all the same
    jnp_after = [hw.jnp_dispatch_count for hw in planes]
    if use_kernels:
        assert jnp_after == jnp_before
    for gid, ctx in enumerate(singles):
        assert mg.group_log[gid] == ctx.delivered_log, gid
        for a, b in zip(_group_state(mg.hw, gid), _group_state(ctx.hw, gid), strict=True):
            np.testing.assert_array_equal(a, b)
    # every submission in every group was delivered exactly once
    assert all(len(log) == 6 for log in mg.group_log)


@pytest.mark.parametrize("use_kernels", [False, True])
def test_idle_group_unperturbed_under_skewed_load(use_kernels):
    """All traffic to group 0, enough to lap its ring: the idle group 1 must
    burn no ring instances, accrete no learned entries, and keep device state
    bit-identical to a deployment that was never pumped — then still serve
    traffic when it finally arrives."""
    cfg = PaxosConfig(n_acceptors=3, n_instances=64, batch=16, n_groups=2)
    ctx = PaxosContext(cfg, use_kernels=use_kernels)
    ref = PaxosContext(
        PaxosConfig(n_acceptors=3, n_instances=64, batch=16),
        use_kernels=use_kernels,
        fused=True,
    )
    for w in range(12):  # 12*16 = 192 instances: laps the 64-slot ring 3x
        for k in range(16):
            ctx.submit(f"w{w}k{k}".encode(), group=0)
        ctx.run_until_quiescent()
    assert len(ctx.group_log[0]) == 192 and len(ctx.group_log[1]) == 0
    assert ctx.hw.next_inst_host[1] == 0
    assert not ctx.learned_g[1]
    for a, b in zip(_group_state(ctx.hw, 1), _group_state(ref.hw, 0), strict=True):
        np.testing.assert_array_equal(a, b)
    ctx.submit(b"late", group=1)
    ctx.run_until_quiescent()
    assert [p for _i, p in ctx.group_log[1]] == [b"late"]


def test_group_recover_targets_one_group():
    """paxos_recover on a multi-group context fills the gap in the addressed
    group with a no-op without disturbing the other groups' rings."""
    mg = PaxosContext(CFG_MG)
    _run_schedule(mg, range(G), waves=2, use_groups=True)
    before = [_group_state(mg.hw, gid) for gid in range(G)]

    # instance beyond the watermark of group 3: phase 1 finds nothing voted,
    # a no-op is decided into it (and discarded by the application layer)
    mg.recover(100, group=3)
    mg.pump()

    after = [_group_state(mg.hw, gid) for gid in range(G)]
    for gid in range(G):
        if gid == 3:
            continue
        for a, b in zip(before[gid], after[gid], strict=True):
            np.testing.assert_array_equal(a, b)
    # group 3's ring now holds a vote for instance 100
    assert np.asarray(mg.hw.stack.vrnd)[3, :, 100 % CFG_MG.n_instances].max() >= 0
    # the no-op was never surfaced to the application
    assert all(len(log) == 2 for log in mg.group_log)


# ---------------------------------------------------------------------------
# Dynamic membership: the free-list over the group axis (DESIGN.md §7)
# ---------------------------------------------------------------------------
def test_membership_freelist_deterministic_and_bounded():
    """retire returns slots to a sorted free-list; create claims the lowest;
    capacity is a hard bound; retired groups reject every group op."""
    cfg = PaxosConfig(n_acceptors=3, n_instances=64, batch=8, n_groups=4)
    hw = MultiGroupDataplane(cfg)
    with pytest.raises(RuntimeError):
        hw.create_group()                      # at capacity
    hw.retire_group(3)
    hw.retire_group(1)
    assert hw.live_groups() == [0, 2]
    with pytest.raises(ValueError):
        hw.retire_group(1)                     # already retired
    assert hw.create_group() == 1              # lowest free slot first
    assert hw.create_group() == 3
    assert hw.live_groups() == [0, 1, 2, 3]
    # context-level: submit/recover/failover on a retired group raise
    ctx = PaxosContext(cfg)
    ctx.retire_group(2)
    for call in (
        lambda: ctx.submit(b"x", group=2),
        lambda: ctx.recover(0, group=2),
        lambda: ctx.fail_coordinator(group=2),
        lambda: ctx.retire_group(2),
    ):
        with pytest.raises(ValueError):
            call()


def test_retire_flushes_in_flight_traffic_before_slot_reuse():
    """Regression: a submit queued on the net but not yet pumped, followed
    by retire + create before the next pump, must NOT leak the old tenant's
    payload into the recycled slot's log or poison its (group, seq) dedup
    space — the retire flushes the tenant's in-flight coordinator traffic."""
    cfg = PaxosConfig(n_acceptors=3, n_instances=64, batch=8, n_groups=2)
    ctx = PaxosContext(cfg)
    ctx.submit(b"stale", group=1)          # queued in flight, never pumped
    ctx.retire_group(1)
    assert ctx.create_group() == 1
    ctx.pump()
    assert ctx.group_log[1] == []          # the old tenant's value is gone
    # the new tenant's seq space is clean: its seq-0 value delivers
    ctx.submit(b"fresh", group=1)
    ctx.run_until_quiescent()
    assert [p for _i, p in ctx.group_log[1]] == [b"fresh"]
    assert not ctx._pending
    # and an in-flight recover() to the dead group is flushed too
    ctx.submit(b"keep", group=0)
    ctx.recover(5, group=1)
    ctx.retire_group(1)
    ctx.run_until_quiescent()
    assert [p for _i, p in ctx.group_log[0]] == [b"keep"]


def test_retire_drains_learner_ring_and_touches_no_other_group():
    """The drained log carries the decided values still resident in the
    retiring group's dedup ring, in instance order; every other group's
    slab state is bit-untouched by retire AND by the subsequent create."""
    ctx = PaxosContext(CFG_MG)
    _run_schedule(ctx, range(G), waves=2, use_groups=True)
    others_before = [_group_state(ctx.hw, gid) for gid in range(G) if gid != 1]
    expect = [
        (inst, np.frombuffer(raw, "<i4")[0])
        for inst, raw in ctx.hw.retire_group(1)
        if np.frombuffer(raw, "<i4")[0] != -0x7FFFFFFF   # skip NOP fillers
    ]
    # decided client values of group 1 in instance order (2 waves, batch>=2)
    assert [inst for inst, _ in expect] == sorted(inst for inst, _ in expect)
    assert len(expect) == 2
    assert ctx.hw.create_group() == 1
    others_after = [_group_state(ctx.hw, gid) for gid in range(G) if gid != 1]
    for before, after in zip(others_before, others_after, strict=True):
        for a, b in zip(before, after, strict=True):
            np.testing.assert_array_equal(a, b)
    # the recycled slot is a fresh deployment
    fresh = MultiGroupDataplane(PaxosConfig(
        n_acceptors=3, n_instances=512, batch=16, n_groups=1))
    for a, b in zip(_group_state(ctx.hw, 1), _group_state(fresh, 0), strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("use_kernels", [False, True])
def test_vacant_slot_rides_folded_dispatch_inert(use_kernels):
    """A vacant (retired) slot with a divergent watermark must not break the
    lockstep fold: the plan still folds the full width, the kernel's
    enabled-mask path substitutes the block's ring offset, and the vacant
    slot's slab stays bit-identical while live groups decide normally."""
    cfg = PaxosConfig(n_acceptors=3, n_instances=64, batch=8, n_groups=4)
    ctx = PaxosContext(cfg, use_kernels=use_kernels)
    # advance all groups, then retire group 0 and recreate it: its fresh
    # watermark (0) diverges from the other groups' (8)
    for gid in range(4):
        ctx.submit(f"a{gid}".encode(), group=gid)
    ctx.run_until_quiescent()
    ctx.retire_group(0)
    assert ctx.create_group() == 0
    assert ctx.hw.next_inst_host == [0, 8, 8, 8]
    # a burst over groups 1..3 (group 0 idle): enabled lockstep folds wide
    enabled, nblk, gb = ctx.hw._plan_round(8, [False, True, True, True])
    assert gb == 4 and (nblk is not None) == use_kernels
    vacant_before = [np.asarray(x) for x in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda s: s[0], (ctx.hw.stack, ctx.hw.lstate))
    )]
    for gid in range(1, 4):
        ctx.submit(f"b{gid}".encode(), group=gid)
    ctx.run_until_quiescent()
    for gid in range(1, 4):
        assert [p for _i, p in ctx.group_log[gid]] == [
            f"a{gid}".encode(), f"b{gid}".encode()
        ]
    vacant_after = [np.asarray(x) for x in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda s: s[0], (ctx.hw.stack, ctx.hw.lstate))
    )]
    for a, b in zip(vacant_before, vacant_after, strict=True):
        np.testing.assert_array_equal(a, b)
    # the recycled group then serves from its own (divergent) watermark
    ctx.submit(b"late", group=0)
    ctx.run_until_quiescent()
    assert [p for _i, p in ctx.group_log[0]] == [b"late"]


def test_session_routing_deterministic_and_balanced():
    n_groups = 8
    ids = [f"session-{i}" for i in range(400)]
    groups = [session_group(s, n_groups) for s in ids]
    # deterministic
    assert groups == [session_group(s, n_groups) for s in ids]
    # every group sees traffic, no group dominates
    counts = np.bincount(groups, minlength=n_groups)
    assert (counts > 0).all()
    assert counts.max() < len(ids) // 2
    # int and bytes session ids route too
    assert 0 <= session_group(12345, n_groups) < n_groups
    assert 0 <= session_group(b"\x00\xff", n_groups) < n_groups


def test_consensus_service_routes_and_delivers():
    svc = ConsensusService(PaxosContext(CFG_MG))
    sessions = [f"user-{i}" for i in range(12)]
    routed = {}
    for k in range(3):
        for s in sessions:
            ticket = svc.session(s).submit(f"{s}:op{k}".encode())
            assert routed.setdefault(s, ticket.group) == ticket.group
    svc.run_until_quiescent()

    assert svc.ctx.stats["delivered"] == 3 * len(sessions)
    assert sum(svc.group_loads()) == 3 * len(sessions)
    for s in sessions:
        log = svc.session(s).delivered()
        mine = [p for _inst, p in log if p.startswith(f"{s}:".encode())]
        # the session observes its own ops in submission order, totally
        # ordered within its group
        assert mine == [f"{s}:op{k}".encode() for k in range(3)]
    # group logs partition the traffic
    assert sum(len(log) for log in svc.ctx.group_log) == 3 * len(sessions)
