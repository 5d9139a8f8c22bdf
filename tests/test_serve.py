"""Serving-engine tests: session -> group -> shard routing of the consensus
tier, routing epochs, and the service's pump."""
from __future__ import annotations

from _hypothesis_compat import given, settings, st
from repro.core import PaxosConfig, PaxosContext
from repro.launch.mesh import make_group_mesh
from repro.serve.engine import ConsensusService

# ---------------------------------------------------------------------------
# Consensus tier: session -> group routing vs group -> shard placement
# ---------------------------------------------------------------------------
def _slab_placements(n_groups):
    """Every contiguous-slab placement of G groups onto a shard count that
    tiles G — the placements ``ShardedMultiGroupDataplane`` can produce."""
    return [
        [gid // (n_groups // n_sh) for gid in range(n_groups)]
        for n_sh in range(1, n_groups + 1)
        if n_groups % n_sh == 0
    ]


class _FakeShardedHw:
    """A dataplane stub with an arbitrary group -> shard placement, so the
    routing property can be tested against placements the in-process
    single-device mesh cannot produce."""

    def __init__(self, placement):
        self._placement = list(placement)

    def group_placement(self):
        return list(self._placement)

    def shard_of_group(self, gid):
        return self._placement[gid]


def _service_with_placement(n_groups, placement):
    import types

    ctx = types.SimpleNamespace(
        cfg=PaxosConfig(n_groups=n_groups), hw=_FakeShardedHw(placement)
    )
    return ConsensusService(ctx)


@settings(max_examples=60, deadline=None)
@given(
    sid=st.one_of(
        st.text(max_size=64),
        st.binary(max_size=64),
        st.integers(min_value=-(2**128), max_value=2**128),
    )
)
def test_session_routing_stable_across_placements(sid):
    """Re-placing groups over a different mesh must never move a session
    between groups: the service's session -> group routing is identical
    under every placement (it consults only the session id and G), while
    the *shard* the session lands on is exactly the group's placement."""
    n_groups = 8
    services = [
        _service_with_placement(n_groups, p)
        for p in _slab_placements(n_groups)
    ]
    gids = [svc.group_of(sid) for svc in services]
    assert len(set(gids)) == 1                     # placement-independent
    gid = gids[0]
    assert 0 <= gid < n_groups
    for svc, placement in zip(services, _slab_placements(n_groups), strict=True):
        assert svc.shard_of(sid) == placement[gid]
        assert svc.group_placement() == placement


def test_session_routing_stable_across_placements_deterministic():
    """Hypothesis-free twin of the property above (runs in runtime-only
    environments where hypothesis is absent)."""
    n_groups = 8
    placements = _slab_placements(n_groups)
    services = [_service_with_placement(n_groups, p) for p in placements]
    for sid in [f"sess-{i}" for i in range(64)] + [b"\x00\xff", 12345, 0]:
        gids = {svc.group_of(sid) for svc in services}
        assert len(gids) == 1, sid
        gid = gids.pop()
        for svc, placement in zip(services, placements, strict=True):
            assert svc.shard_of(sid) == placement[gid]


def test_consensus_service_routing_stable_under_sharding():
    """End to end: the same session lands on the same group id whether the
    dataplane is unsharded or sharded, and ``shard_of`` is exactly the
    placement of that group."""
    cfg = PaxosConfig(n_acceptors=3, n_instances=128, batch=16, n_groups=4)
    base = ConsensusService(PaxosContext(cfg))
    sharded = ConsensusService(PaxosContext(cfg, mesh=make_group_mesh()))
    assert base.group_placement() == [0] * 4       # degenerate one shard
    placement = sharded.group_placement()
    assert len(placement) == 4
    for i in range(50):
        s = f"sess-{i}"
        assert base.group_of(s) == sharded.group_of(s)
        assert sharded.shard_of(s) == placement[sharded.group_of(s)]
    # the sharded service still decides and orders per session
    sids = [f"u{i}" for i in range(6)]
    for k in range(2):
        for s in sids:
            sharded.session(s).submit(f"{s}:op{k}".encode())
    sharded.run_until_quiescent()
    for s in sids:
        mine = [
            p for p in sharded.session(s).read()
            if p.startswith(f"{s}:".encode())
        ]
        assert mine == [f"{s}:op{k}".encode() for k in range(2)]


# ---------------------------------------------------------------------------
# Routing epochs: dynamic membership through the serving tier
# ---------------------------------------------------------------------------
def test_delivered_uniform_group_log_g1():
    """The G == 1 special case is gone: ``delivered`` reads the group log on
    every context shape — ungrouped single-group, grouped single-group
    (mesh), and a multi-group service passing through G == 1 transiently."""
    cfg1 = PaxosConfig(n_acceptors=3, n_instances=128, batch=16)
    for ctx in (
        PaxosContext(cfg1, fused=True),                      # ungrouped
        PaxosContext(cfg1, mesh=make_group_mesh()),          # grouped G=1
    ):
        svc = ConsensusService(ctx)
        sess = svc.session("sess")
        for k in range(3):
            sess.submit(f"op{k}".encode())
        svc.run_until_quiescent()
        log = sess.delivered()
        assert [p for _i, p in log] == [f"op{k}".encode() for k in range(3)]
        # the uniform path and the historical delivered_log read agree
        assert log == list(ctx.delivered_log)


def test_routing_epoch_reroutes_and_stitches():
    """Retiring a group re-routes its sessions deterministically over the
    live set at the epoch bump, and ``delivered`` stitches the archived
    pre-retirement log in front of the new group's log.  Creating a group
    bumps the epoch again and restores the capacity routing."""
    cfg = PaxosConfig(n_acceptors=3, n_instances=128, batch=16, n_groups=4)
    svc = ConsensusService(PaxosContext(cfg))
    sids = [f"sess-{i}" for i in range(32)]
    base_route = {s: svc.group_of(s) for s in sids}
    victim = base_route[sids[0]]
    victims = [s for s in sids if base_route[s] == victim]
    for s in sids:
        svc.session(s).submit(f"{s}:op0".encode())
    svc.run_until_quiescent()
    epoch0 = svc.routing_epoch

    svc.retire_group(victim)
    assert svc.routing_epoch == epoch0 + 1
    live = [g for g in range(4) if g != victim]
    for s in sids:
        gid = svc.group_of(s)
        assert gid in live
        if base_route[s] != victim:
            assert gid == base_route[s]      # survivors keep their pin
    # re-route is deterministic: same live set -> same resolution
    assert [svc.group_of(s) for s in sids] == [svc.group_of(s) for s in sids]

    for s in sids:
        svc.session(s).submit(f"{s}:op1".encode())
    svc.run_until_quiescent()
    for s in victims:
        log = svc.session(s).read()
        # pre-retirement log of the dead group stitched before the live log
        assert f"{s}:op0".encode() in log and f"{s}:op1".encode() in log
        assert log.index(f"{s}:op0".encode()) < log.index(f"{s}:op1".encode())

    gid = svc.create_group()
    assert gid == victim                       # lowest free slot
    assert svc.routing_epoch == epoch0 + 2
    # full capacity again: routing returns to the placement-independent hash
    for s in sids:
        assert svc.group_of(s) == base_route[s]
    # a victim session now routes back to the recycled slot; its view still
    # stitches generation 0's archive, the interim group, then the fresh log
    for s in victims:
        svc.session(s).submit(f"{s}:op2".encode())
    svc.run_until_quiescent()
    for s in victims:
        log = svc.session(s).read()
        ops = [
            log.index(f"{s}:op{k}".encode()) for k in range(3)
        ]
        assert ops == sorted(ops), (s, log)


def test_run_until_quiescent_refreshes_service_loads_per_round():
    """The planner's serving-tier load snapshot must be observed per pumped
    round, not once before the loop: delivery callbacks can submit fresh
    traffic mid-quiescence-run, and the final ``plan_report`` must reflect
    the loads as of the last pumped round (DESIGN.md §11 hardening)."""
    cfg = PaxosConfig(
        n_acceptors=3, n_instances=1 << 9, value_words=4, batch=16,
        n_groups=2,
    )
    ctx = PaxosContext(cfg)
    svc = ConsensusService(ctx)
    first, second = "load-a", "load-b"
    fired = []

    def follow_up(payload, size, inst):
        if not fired:
            fired.append(inst)
            for j in range(5):
                svc.session(second).submit(f"follow-{j}".encode())

    ctx.deliver_cb = follow_up
    for i in range(24):
        svc.session(first).submit(f"lead-{i}".encode())
    loads_before = svc.group_loads()
    svc.run_until_quiescent()
    assert fired and ctx.quiescent()
    report = svc.plan_report()
    # freshness: the report carries the loads INCLUDING the mid-run
    # follow-ups, exactly what group_loads() reads now
    assert report["service_loads"] == svc.group_loads()
    assert report["service_loads"] != loads_before
    assert len(svc.session(second).delivered()) == 5
