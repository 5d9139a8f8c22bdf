"""Adversarial parity: the fused wire path vs the scalar ``paxos.py`` oracle.

Drives randomized multi-round schedules through BOTH fused wire-path
implementations — the jnp ``batched.fused_round`` and the Pallas megakernel
``kernels.wirepath.wirepath_round`` (interpret mode) — and checks them
bit-for-bit against the scalar role state machines of ``core.paxos``:
``Coordinator.on_submit`` -> ``Acceptor.on_p2a`` per live acceptor ->
``Learner.on_p2b`` quorum, plus a ring-dedup mirror of ``LearnerState``.

Schedules include dead/revived acceptors mid-stream (frozen register files),
coordinator round bumps (takeover-style re-proposal over already-voted
slots, i.e. duplicate instances at the slot level), and enough rounds to
wrap the instance ring several times at the ``n_instances`` boundary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched
from repro.core.paxos import Acceptor, Coordinator, Learner, Msg
from repro.core.types import MSG_P2A, MSG_P2B, AcceptorState, CoordinatorState

from repro.kernels import wirepath

NO_ROUND = -1


class _ScalarWirePath:
    """The scalar-oracle mirror of one fused Phase-2 round.

    Sequencing, voting and quorum counting are the unmodified ``core.paxos``
    roles; only the bounded dedup memory (the ring) is modelled here, since
    the scalar Learner's dict is unbounded by construction.
    """

    def __init__(self, n_acceptors: int, n_instances: int):
        self.n = n_instances
        self.co = Coordinator(cid=0, n_instances=n_instances)
        self.acceptors = [
            Acceptor(aid=i, n_instances=n_instances) for i in range(n_acceptors)
        ]
        self.learner = Learner(lid=0, n_acceptors=n_acceptors)
        # LearnerState ring mirror: slot -> (inst, value)
        self.ring: dict = {}

    def round(self, values: np.ndarray, alive: np.ndarray):
        b, v = values.shape
        fresh = np.zeros((b,), bool)
        win = np.full((b,), NO_ROUND, np.int32)
        out_val = np.zeros((b, v), np.int32)
        for j in range(b):
            p2a = self.co.on_submit(Msg(5, value=values[j]))
            votes = []
            for aid, acc in enumerate(self.acceptors):
                if not alive[aid]:
                    continue  # crashed switch: BRAM frozen, emits nothing
                out = acc.on_p2a(
                    Msg(MSG_P2A, inst=p2a.inst, rnd=p2a.rnd, value=values[j])
                )
                if out.msgtype == MSG_P2B:
                    votes.append((aid, out))
            decided = None
            for aid, out in votes:
                d = self.learner.on_p2b(
                    Msg(MSG_P2B, inst=out.inst, rnd=out.rnd, vrnd=out.vrnd,
                        swid=aid, value=out.value)
                )
                if d is not None:
                    decided = d
            if decided is not None:
                win[j] = decided.rnd
                out_val[j] = decided.value
                slot = decided.inst % self.n
                prev = self.ring.get(slot)
                if prev is None or prev[0] != decided.inst:
                    fresh[j] = True
                    self.ring[slot] = (decided.inst, decided.value.copy())
        return fresh, win, out_val


def _mk_device_state(a: int, n: int, v: int):
    one = AcceptorState.init(n, v)
    stack = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (a,) + x.shape).copy(), one
    )
    return (
        CoordinatorState.init(),
        stack,
        batched.LearnerState.init(n, v),
    )


def _schedule(seed: int, rounds: int, a: int):
    """Random alive masks + round bumps; at least quorum alive most rounds."""
    rng = np.random.default_rng(seed)
    sched = []
    crnd = 0
    for _ in range(rounds):
        alive = rng.random(a) > 0.25
        if rng.random() < 0.2:
            crnd += int(rng.integers(1, 3))
        sched.append((alive, crnd))
    return sched


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("n,b,v,a", [(256, 32, 4, 3), (128, 64, 2, 5)])
def test_fused_round_matches_scalar_oracle(seed, n, b, v, a):
    """Multi-round randomized schedule, ring wraps several times."""
    rng = np.random.default_rng(seed)
    rounds = 2 * n // b + 3  # guarantees ring wraparound at the N boundary
    quorum = a // 2 + 1

    cstate, stack, lstate = _mk_device_state(a, n, v)
    cstate_k, stack_k, lstate_k = _mk_device_state(a, n, v)
    oracle = _ScalarWirePath(a, n)

    # pre-seed promised rounds above the initial crnd so the schedule
    # exercises the reject path (recovery-touched slots) until crnd catches up
    seed_rnd = rng.integers(0, 4, (a, n)).astype(np.int32)
    stack = AcceptorState(jnp.asarray(seed_rnd), stack.vrnd, stack.value)
    stack_k = AcceptorState(jnp.asarray(seed_rnd), stack_k.vrnd, stack_k.value)
    for aid in range(a):
        for slot in np.nonzero(seed_rnd[aid])[0]:
            oracle.acceptors[aid].slots[int(slot)] = (
                int(seed_rnd[aid, slot]), NO_ROUND, np.zeros((v,), np.int32)
            )

    for alive, crnd in _schedule(seed, rounds, a):
        values = rng.integers(-99, 99, (b, v)).astype(np.int32)
        active = jnp.ones((b,), bool)
        cstate = CoordinatorState(next_inst=cstate.next_inst, crnd=jnp.int32(crnd))
        cstate_k = CoordinatorState(
            next_inst=cstate_k.next_inst, crnd=jnp.int32(crnd)
        )
        oracle.co.crnd = crnd

        cstate, stack, lstate, fresh, inst, win, value = batched.fused_round(
            cstate, stack, lstate, jnp.asarray(values), active,
            jnp.asarray(alive), quorum,
        )
        outs = wirepath.wirepath_round(
            cstate_k.next_inst, cstate_k.crnd, jnp.int32(quorum),
            jnp.asarray(alive, jnp.int32),
            stack_k.rnd, stack_k.vrnd, stack_k.value,
            lstate_k.delivered, lstate_k.inst, lstate_k.value,
            jnp.asarray(values), interpret=True,
        )
        (k_rnd, k_vrnd, k_val, k_ldel, k_linst, k_lval,
         k_fresh, k_win, k_value) = outs
        stack_k = AcceptorState(k_rnd, k_vrnd, k_val)
        lstate_k = batched.LearnerState(k_ldel, k_linst, k_lval)
        cstate_k = CoordinatorState(
            next_inst=cstate_k.next_inst + b, crnd=cstate_k.crnd
        )

        o_fresh, o_win, o_value = oracle.round(values, alive)

        # Pallas megakernel == jnp fused round, bit for bit, ALL positions
        np.testing.assert_array_equal(np.asarray(fresh), np.asarray(k_fresh) != 0)
        np.testing.assert_array_equal(np.asarray(win), np.asarray(k_win))
        np.testing.assert_array_equal(np.asarray(value), np.asarray(k_value))
        for x, y in zip(jax.tree_util.tree_leaves((stack, lstate)),
                        jax.tree_util.tree_leaves((stack_k, lstate_k)), strict=True):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

        # fused round == scalar oracle
        np.testing.assert_array_equal(np.asarray(fresh), o_fresh)
        np.testing.assert_array_equal(
            np.asarray(win)[o_fresh], o_win[o_fresh]
        )
        np.testing.assert_array_equal(
            np.asarray(value)[o_fresh], o_value[o_fresh]
        )

    # final acceptor register files agree with the scalar acceptors
    h_rnd = np.asarray(stack.rnd)
    h_vrnd = np.asarray(stack.vrnd)
    h_val = np.asarray(stack.value)
    for aid, acc in enumerate(oracle.acceptors):
        for slot, (rnd, vrnd, val) in acc.slots.items():
            assert h_rnd[aid, slot] == rnd, (aid, slot)
            assert h_vrnd[aid, slot] == vrnd, (aid, slot)
            np.testing.assert_array_equal(h_val[aid, slot], val)


def test_fused_round_ring_wraparound_boundary():
    """A window crossing the N boundary wraps block indices and redelivers
    fresh instances into previously-used slots."""
    n, b, v, a = 128, 32, 2, 3
    rng = np.random.default_rng(9)
    cstate, stack, lstate = _mk_device_state(a, n, v)
    alive = jnp.ones((a,), bool)
    seen_vals = []
    # 5 rounds of 32 = 160 instances: wraps at round 5 (inst 128..159 reuse
    # slots 0..31, which already hold delivered instances 0..31)
    for r in range(5):
        values = rng.integers(0, 100, (b, v)).astype(np.int32)
        seen_vals.append(values)
        cstate, stack, lstate, fresh, inst, win, value = batched.fused_round(
            cstate, stack, lstate, jnp.asarray(values),
            jnp.ones((b,), bool), alive, 2,
        )
        # wraparound must NOT suppress fresh instances reusing a slot
        assert np.asarray(fresh).all(), f"round {r}"
        np.testing.assert_array_equal(np.asarray(value), values)
    # slots 0..31 now hold the round-4 instances (128..159), not 0..31
    np.testing.assert_array_equal(
        np.asarray(lstate.inst)[:b], np.arange(4 * b, 5 * b)
    )
    np.testing.assert_array_equal(np.asarray(lstate.value)[:b], seen_vals[4])


def test_fused_round_duplicate_instance_suppressed():
    """Re-running the sequencer over the same window (stale watermark after a
    failover rollback) re-decides the same instances; dedup must mark them
    stale, not fresh."""
    n, b, v, a = 128, 16, 2, 3
    rng = np.random.default_rng(3)
    values = rng.integers(0, 50, (b, v)).astype(np.int32)
    cstate, stack, lstate = _mk_device_state(a, n, v)
    alive = jnp.ones((a,), bool)
    _, stack, lstate, fresh, _, _, _ = batched.fused_round(
        cstate, stack, lstate, jnp.asarray(values), jnp.ones((b,), bool),
        alive, 2,
    )
    assert np.asarray(fresh).all()
    # replay the SAME window (cstate was not advanced) at a higher round
    cstate2 = CoordinatorState(next_inst=jnp.int32(0), crnd=jnp.int32(5))
    _, stack, lstate, fresh2, _, win2, val2 = batched.fused_round(
        cstate2, stack, lstate, jnp.asarray(values), jnp.ones((b,), bool),
        alive, 2,
    )
    # decided again (Paxos re-decides the same value at the higher round)...
    assert (np.asarray(win2) == 5).all()
    np.testing.assert_array_equal(np.asarray(val2), values)
    # ...but delivery is suppressed as a duplicate
    assert not np.asarray(fresh2).any()


# ---------------------------------------------------------------------------
# Multi-group parity: G fused groups == G independent single-group runs
# ---------------------------------------------------------------------------
def _mk_mg_state(g: int, a: int, n: int, v: int):
    return batched.init_multigroup_state(g, a, n, v)


@pytest.mark.parametrize("g", [1, 4, 8])
def test_multigroup_fused_matches_independent_runs(g):
    """The G-group fused round (Pallas kernel, both group->grid mappings, and
    the vmapped jnp oracle) is bit-identical to G *independent* single-group
    ``fused_round`` executions and to G independent scalar oracles — through
    per-group dead acceptors, a mid-schedule coordinator failover in one
    group (round bump + watermark jump), and ring wraparound."""
    a, n, b, v = 3, 256, 32, 4
    quorum = a // 2 + 1
    rounds = 2 * n // b + 3  # wraps each group's ring
    fail_group = g - 1       # the group that loses its coordinator
    fail_round = rounds // 2
    rng = np.random.default_rng(g)

    cstate, stack, lstate = _mk_mg_state(g, a, n, v)
    cstate_k, stack_k, lstate_k = _mk_mg_state(g, a, n, v)
    # independent single-group references + scalar oracles, one per group
    ind = [_mk_device_state(a, n, v) for _ in range(g)]
    oracles = [_ScalarWirePath(a, n) for _ in range(g)]

    crnd_host = np.zeros((g,), np.int32)
    ni_host = np.zeros((g,), np.int32)
    lockstep = True
    for r in range(rounds):
        # per-group liveness: quorum always alive, the rest random
        alive = rng.random((g, a)) > 0.3
        alive[:, :quorum] = True
        if r == fail_round:
            # takeover in ONE group: strictly higher unique round, watermark
            # jumps forward past the uncertainty window (block-aligned)
            crnd_host[fail_group] += 7
            ni_host[fail_group] += 2 * b
            lockstep = False
            for gid in range(g):
                oracles[gid].co.crnd = int(crnd_host[gid])
            oracles[fail_group].co.next_inst = int(ni_host[fail_group])
        values = rng.integers(-99, 99, (g, b, v)).astype(np.int32)

        cstate = CoordinatorState(
            next_inst=jnp.asarray(ni_host), crnd=jnp.asarray(crnd_host)
        )
        cstate_k = CoordinatorState(
            next_inst=jnp.asarray(ni_host), crnd=jnp.asarray(crnd_host)
        )

        # jnp multigroup oracle
        cstate, stack, lstate, fresh, inst, win, value = (
            batched.multigroup_fused_round(
                cstate, stack, lstate, jnp.asarray(values),
                jnp.ones((g, b), bool), jnp.asarray(alive), quorum,
            )
        )
        # Pallas megakernel, one group per grid step (general mapping) and —
        # while the watermarks are in lockstep — 2, 4 or all groups folded
        # per step, their rows run as a loop.  EVERY mapping must match the
        # jnp oracle bit for bit (both calls see the same pre-round state;
        # no donation at this call level).
        group_blocks = (1,)
        if lockstep:
            group_blocks += tuple(gb for gb in (2, 4, 8) if g % gb == 0)
        for gb in group_blocks:
            outs = wirepath.multigroup_wirepath_round(
                cstate_k.next_inst, cstate_k.crnd, jnp.int32(quorum),
                jnp.asarray(alive, jnp.int32),
                stack_k.rnd, stack_k.vrnd, stack_k.value,
                lstate_k.delivered, lstate_k.inst, lstate_k.value,
                jnp.asarray(values), group_block=gb, interpret=True,
            )
            np.testing.assert_array_equal(
                np.asarray(fresh), np.asarray(outs[6]) != 0, err_msg=f"gb={gb}"
            )
            np.testing.assert_array_equal(
                np.asarray(win), np.asarray(outs[7]), err_msg=f"gb={gb}"
            )
            np.testing.assert_array_equal(
                np.asarray(value), np.asarray(outs[8]), err_msg=f"gb={gb}"
            )
            for x, y in zip(jax.tree_util.tree_leaves((stack, lstate)),
                            outs[:6], strict=True):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y), err_msg=f"gb={gb}"
                )
        (k_rnd, k_vrnd, k_val, k_ldel, k_linst, k_lval,
         k_fresh, k_win, k_value) = outs
        stack_k = AcceptorState(k_rnd, k_vrnd, k_val)
        lstate_k = batched.LearnerState(k_ldel, k_linst, k_lval)

        for gid in range(g):
            # fused group slice == independent single-group fused_round
            c_g, st_g, ls_g = ind[gid]
            c_g = CoordinatorState(
                next_inst=jnp.int32(ni_host[gid]), crnd=jnp.int32(crnd_host[gid])
            )
            c_g, st_g, ls_g, f_g, i_g, w_g, v_g = batched.fused_round(
                c_g, st_g, ls_g, jnp.asarray(values[gid]),
                jnp.ones((b,), bool), jnp.asarray(alive[gid]), quorum,
            )
            ind[gid] = (c_g, st_g, ls_g)
            np.testing.assert_array_equal(np.asarray(fresh[gid]), np.asarray(f_g))
            np.testing.assert_array_equal(np.asarray(inst[gid]), np.asarray(i_g))
            np.testing.assert_array_equal(np.asarray(win[gid]), np.asarray(w_g))
            np.testing.assert_array_equal(np.asarray(value[gid]), np.asarray(v_g))
            for x, y in zip(
                jax.tree_util.tree_leaves(
                    jax.tree_util.tree_map(lambda s, gid=gid: s[gid], (stack, lstate))
                ),
                jax.tree_util.tree_leaves((st_g, ls_g)), strict=True,
            ):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

            # fused group slice == the group's independent scalar oracle
            o_fresh, o_win, o_value = oracles[gid].round(values[gid], alive[gid])
            np.testing.assert_array_equal(np.asarray(fresh[gid]), o_fresh)
            np.testing.assert_array_equal(
                np.asarray(win[gid])[o_fresh], o_win[o_fresh]
            )
            np.testing.assert_array_equal(
                np.asarray(value[gid])[o_fresh], o_value[o_fresh]
            )

        ni_host += b

    # final per-group register files agree with each group's scalar acceptors
    h_rnd = np.asarray(stack.rnd)
    h_vrnd = np.asarray(stack.vrnd)
    for gid in range(g):
        for aid, acc in enumerate(oracles[gid].acceptors):
            for slot, (rnd, vrnd, _val) in acc.slots.items():
                assert h_rnd[gid, aid, slot] == rnd, (gid, aid, slot)
                assert h_vrnd[gid, aid, slot] == vrnd, (gid, aid, slot)


def test_multigroup_dead_acceptor_isolated_to_group():
    """Killing an acceptor in one group changes nothing in any other group:
    the others' outputs and register files stay bit-identical to an all-alive
    run, and the victim group still delivers through its quorum."""
    g, a, n, b, v = 4, 3, 128, 32, 2
    rng = np.random.default_rng(7)
    values = jnp.asarray(rng.integers(0, 99, (g, b, v)).astype(np.int32))
    active = jnp.ones((g, b), bool)

    alive_all = jnp.ones((g, a), bool)
    alive_dead = alive_all.at[1, 2].set(False)  # kill acceptor 2 of group 1

    outs = {}
    for key, alive in (("all", alive_all), ("dead", alive_dead)):
        cstate, stack, lstate = _mk_mg_state(g, a, n, v)
        outs[key] = batched.multigroup_fused_round(
            cstate, stack, lstate, values, active, alive, 2
        )
    for x, y in zip(jax.tree_util.tree_leaves(outs["all"]),
                    jax.tree_util.tree_leaves(outs["dead"]), strict=True):
        x, y = np.asarray(x), np.asarray(y)
        mask = np.ones(x.shape[0], bool)
        mask[1] = False  # every group but the victim is untouched
        np.testing.assert_array_equal(x[mask], y[mask])
    # the victim still has quorum (2 of 3) and delivers everything
    fresh_dead = np.asarray(outs["dead"][3])
    assert fresh_dead[1].all()


def test_vote_all_window_kernel_matches_jnp():
    """Staged all-acceptor vote kernel vs the vmapped scatter path."""
    from repro.kernels import ref

    rng = np.random.default_rng(11)
    a, n, b, v = 3, 256, 128, 4
    st_rnd = jnp.asarray(rng.integers(0, 3, (a, n)).astype(np.int32))
    st_vrnd = jnp.asarray(rng.integers(-1, 2, (a, n)).astype(np.int32))
    st_val = jnp.asarray(rng.integers(-9, 9, (a, n, v)).astype(np.int32))
    base = 128  # window [128, 256): block-aligned, wraps on next call
    alive = jnp.asarray([1, 0, 1], jnp.int32)
    mt = jnp.asarray(rng.choice([3, 0], size=b).astype(np.int32))
    mr = jnp.asarray(rng.integers(0, 4, b).astype(np.int32))
    mv = jnp.asarray(rng.integers(-9, 9, (b, v)).astype(np.int32))
    k = wirepath.acceptor_vote_all_window(
        st_rnd, st_vrnd, st_val, base, alive, mt, mr, mv, interpret=True
    )
    r = ref.acceptor_vote_all_window(
        st_rnd, st_vrnd, st_val, base, alive, mt, mr, mv
    )
    for x, y in zip(k, r, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # wrapped follow-up window [256, 384) -> slots [0, 128)
    k2 = wirepath.acceptor_vote_all_window(
        k[0], k[1], k[2], 256, alive, mt, mr, mv, interpret=True
    )
    r2 = ref.acceptor_vote_all_window(
        r[0], r[1], r[2], 256, alive, mt, mr, mv
    )
    for x, y in zip(k2, r2, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Cohort-compacted dispatch (DESIGN.md §8)
# ---------------------------------------------------------------------------
def test_cohort_round_matches_full_width_oracle():
    """``cohort_wirepath_round`` — the group-axis-compacted kernel entry —
    is bit-identical to the full-width jnp oracle with non-members held
    inert, across: a compact single-group hot tier, a folded block carrying
    a disabled member, divergent per-block watermark bases, and multiple
    rounds of watermark advance (the cold tier wrapping its ring slower
    than the hot tier).  Unselected groups' slabs must ride through the
    aliased state outputs bit-unchanged."""
    g, a, n, v = 8, 3, 256, 4
    quorum = a // 2 + 1
    rng = np.random.default_rng(11)
    _cs, stack, ls = _mk_mg_state(g, a, n, v)
    _cso, stack_o, ls_o = _mk_mg_state(g, a, n, v)
    alive = jnp.ones((g, a), jnp.int32)
    marks = np.zeros((g,), np.int32)
    hot = 0
    hot_b, cold_b = 64, 8
    for _ in range(2 * n // hot_b + 2):          # hot ring wraps twice
        # -- hot tier: compact single-group block ---------------------------
        vals_h = rng.integers(-99, 99, (1, hot_b, v)).astype(np.int32)
        en_h = np.zeros((g,), np.int32)
        en_h[hot] = 1
        outs = wirepath.cohort_wirepath_round(
            jnp.asarray([hot], jnp.int32),
            jnp.asarray(marks), jnp.zeros((g,), jnp.int32),
            jnp.int32(quorum), alive,
            stack.rnd, stack.vrnd, stack.value,
            ls.delivered, ls.inst, ls.value,
            jnp.asarray(vals_h), jnp.asarray(en_h),
            group_block=1, interpret=True,
        )
        stack = AcceptorState(*outs[:3])
        ls = batched.LearnerState(*outs[3:6])
        # oracle: full-width with non-members' rounds at NO_ROUND
        vals_f = np.zeros((g, hot_b, v), np.int32)
        vals_f[hot] = vals_h[0]
        eff = CoordinatorState(
            next_inst=jnp.asarray(marks),
            crnd=jnp.where(jnp.asarray(en_h) != 0, 0, NO_ROUND),
        )
        _c, stack_o, ls_o, fresh_o, _i, _w, val_o = (
            batched.multigroup_fused_round(
                eff, stack_o, ls_o, jnp.asarray(vals_f),
                jnp.ones((g, hot_b), bool), alive != 0, quorum,
            )
        )
        np.testing.assert_array_equal(
            np.asarray(outs[6] != 0), np.asarray(fresh_o)[[hot]]
        )
        np.testing.assert_array_equal(
            np.asarray(outs[8]), np.asarray(val_o)[[hot]]
        )
        marks[hot] += hot_b
        # -- cold tier: groups 1..7 folded into one full-width block --------
        vals_c = rng.integers(-99, 99, (g, cold_b, v)).astype(np.int32)
        en_c = np.ones((g,), np.int32)
        en_c[hot] = 0
        outs = wirepath.cohort_wirepath_round(
            jnp.asarray([0], jnp.int32),
            jnp.asarray(marks), jnp.zeros((g,), jnp.int32),
            jnp.int32(quorum), alive,
            stack.rnd, stack.vrnd, stack.value,
            ls.delivered, ls.inst, ls.value,
            jnp.asarray(vals_c), jnp.asarray(en_c),
            group_block=g, interpret=True,
        )
        stack = AcceptorState(*outs[:3])
        ls = batched.LearnerState(*outs[3:6])
        eff = CoordinatorState(
            next_inst=jnp.asarray(marks),
            crnd=jnp.where(jnp.asarray(en_c) != 0, 0, NO_ROUND),
        )
        _c, stack_o, ls_o, fresh_o, _i, _w, val_o = (
            batched.multigroup_fused_round(
                eff, stack_o, ls_o, jnp.asarray(vals_c),
                jnp.ones((g, cold_b), bool), alive != 0, quorum,
            )
        )
        np.testing.assert_array_equal(
            np.asarray(outs[6] != 0), np.asarray(fresh_o)
        )
        cold = [i for i in range(g) if i != hot]
        np.testing.assert_array_equal(
            np.asarray(outs[8])[cold], np.asarray(val_o)[cold]
        )
        marks[[i for i in range(g) if i != hot]] += cold_b
        # full state parity every round: compaction, folding over the
        # disabled hot slot, and untouched-slab aliasing are all state-exact
        for x, y in zip(
            jax.tree_util.tree_leaves((stack, ls)),
            jax.tree_util.tree_leaves((stack_o, ls_o)), strict=True,
        ):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("gb", [2, 4, 8])
def test_cohort_round_per_block_bases(gb):
    """Folded blocks of ``gb`` groups, each at a lockstep base of its own,
    run their group rows as a loop and stay bit-identical to the oracle —
    on random register files and learner rings, so a row that read or
    wrote another row's block would show.  The last member of every block
    is disabled with a watermark that diverged from the block's, so the
    block-base substitution runs through the loop too."""
    g, a, n, v, b = 16, 3, 256, 4, 16
    quorum = a // 2 + 1
    rng = np.random.default_rng(5 + gb)

    def rand(lo, hi, shape):
        return jnp.asarray(rng.integers(lo, hi, shape).astype(np.int32))

    stack = AcceptorState(rand(0, 3, (g, a, n)), rand(-1, 3, (g, a, n)),
                          rand(-99, 99, (g, a, n, v)))
    ls = batched.LearnerState(rand(0, 2, (g, n)), rand(0, 2 * n, (g, n)),
                              rand(-99, 99, (g, n, v)))
    alive = jnp.asarray(rng.random((g, a)) > 0.2, jnp.int32)
    crnd = rand(0, 4, (g,))
    # a window at any offset of its first ring block: base % 128 + b <= 256
    bases = rng.integers(0, 4 * n, g // gb).astype(np.int32)
    marks = np.repeat(bases, gb)
    enabled = np.ones((g,), np.int32)
    enabled[gb - 1::gb] = 0
    marks[gb - 1::gb] += rng.integers(1, n, g // gb).astype(np.int32)
    vals = rng.integers(-99, 99, (g, b, v)).astype(np.int32)
    outs = wirepath.cohort_wirepath_round(
        jnp.arange(g // gb, dtype=jnp.int32),
        jnp.asarray(marks), crnd, jnp.int32(quorum), alive,
        stack.rnd, stack.vrnd, stack.value,
        ls.delivered, ls.inst, ls.value,
        jnp.asarray(vals), jnp.asarray(enabled),
        group_block=gb, interpret=True,
    )
    cs_o = CoordinatorState(
        next_inst=jnp.asarray(marks),
        crnd=jnp.where(jnp.asarray(enabled) != 0, crnd, NO_ROUND),
    )
    _c, stack_o, ls_o, fresh_o, _i, win_o, val_o = (
        batched.multigroup_fused_round(
            cs_o, stack, ls, jnp.asarray(vals),
            jnp.ones((g, b), bool), alive != 0, quorum,
        )
    )
    assert np.asarray(fresh_o).any()
    np.testing.assert_array_equal(np.asarray(outs[6] != 0), np.asarray(fresh_o))
    np.testing.assert_array_equal(np.asarray(outs[7]), np.asarray(win_o))
    np.testing.assert_array_equal(np.asarray(outs[8]), np.asarray(val_o))
    for x, y in zip(
        jax.tree_util.tree_leaves((AcceptorState(*outs[:3]),
                                   batched.LearnerState(*outs[3:6]))),
        jax.tree_util.tree_leaves((stack_o, ls_o)), strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _kernel_eqn_count(group_block: int) -> int:
    """Equations, nested ones included, of the kernel body inside the
    ``pallas_call`` of ``cohort_wirepath_round`` folded at ``group_block``,
    traced from abstract shapes at the tenants deployment's widths."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    g, a, n, v, b = 64, 3, 65536, 16, 128

    def sds(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32)

    traced = wirepath.cohort_wirepath_round.trace(
        sds(g // group_block), sds(g), sds(g), sds(), sds(g, a),
        sds(g, a, n), sds(g, a, n), sds(g, a, n, v), sds(g, n), sds(g, n),
        sds(g, n, v), sds(g, b, v), sds(g), sds(g),
        group_block=group_block, window_blocks=2,
    )

    def subjaxprs(eqn):
        for p in eqn.params.values():
            for x in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(x, ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, Jaxpr):
                    yield x

    def count(jaxpr) -> int:
        return sum(1 + sum(count(j) for j in subjaxprs(e)) for e in jaxpr.eqns)

    [kernel] = [e.params["jaxpr"] for e in traced.jaxpr.eqns
                if e.primitive.name == "pallas_call"]
    return count(kernel)


def test_folded_kernel_trace_does_not_grow_with_the_fold():
    """The group rows of a folded block are one loop, not GB copies: the
    kernel traced at the widest fold (64) is no larger than at a fold of 2,
    give or take a few equations.  Unrolled rows made the deployment's
    five fold-64 programs most of its set-up."""
    assert _kernel_eqn_count(64) <= _kernel_eqn_count(2) + 8
