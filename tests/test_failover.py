"""Fault-tolerance paths of the consensus core: coordinator failover
(hardware -> software takeover), recover() gap fill, replicated log trim."""
from __future__ import annotations

import numpy as np

from repro.core import FaultSpec, PaxosConfig, PaxosContext, ReplicatedLog, SimNet
from repro.core.failover import allocate_round, takeover

CFG = PaxosConfig(n_acceptors=3, n_instances=512, batch=16)


# ---------------------------------------------------------------------------
# coordinator failover
# ---------------------------------------------------------------------------
def test_coordinator_failover_continues_and_preserves_agreement():
    got = []
    ctx = PaxosContext(CFG, deliver=lambda v, n, i: got.append(v))
    for k in range(5):
        ctx.submit(f"pre{k}".encode())
    ctx.run_until_quiescent()
    ctx.fail_coordinator()  # software takeover (paper Fig. 8b)
    for k in range(5):
        ctx.submit(f"post{k}".encode())
    ctx.run_until_quiescent()
    assert {f"pre{k}".encode() for k in range(5)} <= set(got)
    assert {f"post{k}".encode() for k in range(5)} <= set(got)
    # all delivered instances unique
    insts = [i for i, _ in ctx.delivered_log]
    assert len(insts) == len(set(insts))


def test_safe_takeover_reproposes_voted_values():
    """The takeover Phase-1 scan must re-propose (not lose) voted instances."""
    ctx = PaxosContext(CFG)
    for k in range(8):
        ctx.submit(f"val{k}".encode())
    ctx.run_until_quiescent()
    res = takeover(
        ctx.hw, coordinator_id=1, epoch=1,
        est_next_inst=0, window=32, quorum=CFG.quorum,
    )
    assert res.next_inst >= 16  # found the used window (one batch = 16)
    assert len(res.reproposed) >= 8
    assert res.crnd == allocate_round(1, 1)


def test_takeover_odd_window_never_touches_beyond_hi():
    """Regression: when (hi - lo) is not a multiple of cfg.batch, the final
    Phase-1/Phase-2 batch used to overhang the window — bumping promised
    rounds and re-proposing values into instances >= hi, and advancing
    next_inst past the window.  Out-of-window positions must stay
    bit-untouched and ``scanned`` must report the true count."""
    cfg = PaxosConfig(n_acceptors=3, n_instances=64, batch=8)
    ctx = PaxosContext(cfg, fused=True)
    for k in range(16):                    # decide instances 0..15 at round 0
        ctx.submit(f"v{k}".encode())
    ctx.run_until_quiescent()
    before_rnd = np.asarray(ctx.hw.stack.rnd).copy()
    before_vrnd = np.asarray(ctx.hw.stack.vrnd).copy()
    before_val = np.asarray(ctx.hw.stack.value).copy()

    # window [0, 12): 12 is NOT a multiple of batch=8 — the second batch
    # covers [8, 16) and must mask positions 12..15
    res = takeover(
        ctx.hw, coordinator_id=1, epoch=1,
        est_next_inst=4, window=8, quorum=cfg.quorum,
    )
    assert res.scanned == 12               # the true scanned count
    # voted instances inside the window were re-proposed, none beyond it
    assert {i for i, _ in res.reproposed} == set(range(12))
    assert res.next_inst == 12             # not dragged past hi by overscan
    # out-of-window slots 12..15: promised round, vote round and value are
    # bit-identical to the pre-takeover register file
    rnd = np.asarray(ctx.hw.stack.rnd)
    vrnd = np.asarray(ctx.hw.stack.vrnd)
    val = np.asarray(ctx.hw.stack.value)
    np.testing.assert_array_equal(rnd[:, 12:16], before_rnd[:, 12:16])
    np.testing.assert_array_equal(vrnd[:, 12:16], before_vrnd[:, 12:16])
    np.testing.assert_array_equal(val[:, 12:16], before_val[:, 12:16])
    # in-window voted slots really moved to the takeover round
    assert (rnd[:, :12] == res.crnd).all()
    # and every slot outside the final batch's reach is untouched too
    np.testing.assert_array_equal(rnd[:, 16:], before_rnd[:, 16:])


def test_round_allocation_disjoint():
    r1 = {allocate_round(e, 0) for e in range(50)}
    r2 = {allocate_round(e, 1) for e in range(50)}
    assert not (r1 & r2)


# ---------------------------------------------------------------------------
# recover() + replicated log
# ---------------------------------------------------------------------------
def test_recover_fills_learner_gap():
    net = SimNet(FaultSpec(), seed=3)
    got = {}
    ctx = PaxosContext(CFG, deliver=lambda v, n, i: got.__setitem__(i, v), net=net)
    for k in range(4):
        ctx.submit(f"g{k}".encode())
    ctx.run_until_quiescent()
    # wipe learner 0's memory of instance 2 to simulate a missed decision
    inst = sorted(got)[2]
    val = ctx.learned[0].pop(inst)
    got.pop(inst)
    ctx.recover(inst, nop=b"\x00")
    ctx.run_until_quiescent()
    assert inst in ctx.learned[0]
    assert ctx.learned[0][inst] == val  # recovered the decided value, not nop


def test_recover_undetermined_instance_yields_nop():
    ctx = PaxosContext(CFG)
    ctx.recover(100, nop=b"\x00")
    ctx.run_until_quiescent()
    # decided (learned) but filtered from application deliveries as a no-op
    assert 100 in ctx.learned[0]
    assert ctx.stats["delivered"] == 0


def test_replicated_log_order_gaps_trim():
    log = ReplicatedLog(quorum=2)
    applied = []
    log.on_apply = lambda i, p: applied.append(i)
    log.offer(0, b"a")
    log.offer(2, b"c")
    assert applied == [0]
    assert log.gaps(3) == [1]
    log.offer(1, b"b")
    assert applied == [0, 1, 2]
    # trim requires a quorum of learner acks
    assert not log.ack_trim(0, upto=2)
    assert log.ack_trim(1, upto=2)
    assert log.trim_watermark == 2
    log.offer(1, b"zz")  # below watermark: ignored
    assert 1 not in log.entries
