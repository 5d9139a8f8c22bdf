"""Groups-sharded dataplane parity (DESIGN.md §6).

The contract under test: ``ShardedMultiGroupDataplane`` — the multi-group
wire path with its ``(G, A, N)`` slabs partitioned over a ``groups`` mesh
axis via ``shard_map`` — is *bit-identical* to the single-device
``MultiGroupDataplane`` and to G independent scalar ``core.paxos`` oracles,
on both the jnp and Pallas-kernel backends, through frozen groups, dead
acceptors, and ring wraparound.  On the in-process host mesh (1 CPU device)
that pins the degenerate reduction; ``test_sharded_multidevice`` re-runs
the parity on a real 8-shard mesh in a subprocess, with the frozen group
and the dead acceptor living on *distinct shards*.
"""
from __future__ import annotations

import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import (
    MultiGroupDataplane,
    PaxosConfig,
    PaxosContext,
    ShardedMultiGroupDataplane,
)
from repro.core.paxos import Acceptor, Coordinator, Learner, Msg
from repro.core.types import MSG_P2A, MSG_P2B
from repro.launch.mesh import make_group_mesh

N_DEV = len(jax.devices())


class _ScalarGroup:
    """One group's scalar-oracle mirror of the fused Phase-2 round
    (sequencing, per-acceptor votes, learner quorum — unmodified
    ``core.paxos`` roles)."""

    def __init__(self, n_acceptors: int, n_instances: int):
        self.co = Coordinator(cid=0, n_instances=n_instances)
        self.acceptors = [
            Acceptor(aid=i, n_instances=n_instances) for i in range(n_acceptors)
        ]
        self.learner = Learner(lid=0, n_acceptors=n_acceptors)

    def round(self, values: np.ndarray, alive) -> list:
        decided = []
        for j in range(values.shape[0]):
            p2a = self.co.on_submit(Msg(5, value=values[j]))
            d = None
            for aid, acc in enumerate(self.acceptors):
                if not alive[aid]:
                    continue
                out = acc.on_p2a(
                    Msg(MSG_P2A, inst=p2a.inst, rnd=p2a.rnd, value=values[j])
                )
                if out.msgtype == MSG_P2B:
                    got = self.learner.on_p2b(
                        Msg(MSG_P2B, inst=out.inst, rnd=out.rnd,
                            vrnd=out.vrnd, swid=aid, value=out.value)
                    )
                    if got is not None:
                        d = got
            decided.append(d)
        return decided


def _state_leaves(hw):
    return [
        np.asarray(x)
        for x in jax.tree_util.tree_leaves((hw.stack, hw.lstate))
    ]


@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("mult", [1, 2])
def test_sharded_matches_unsharded_and_scalar_oracle(mult, use_kernels):
    """Sharded == unsharded == G scalar oracles, bit for bit, through a
    frozen group, a dead acceptor, and a full ring wrap."""
    g = N_DEV * mult
    cfg = PaxosConfig(n_acceptors=3, n_instances=128, batch=16, n_groups=g)
    mg = MultiGroupDataplane(cfg, use_kernels=use_kernels)
    sh = ShardedMultiGroupDataplane(
        cfg, mesh=make_group_mesh(), use_kernels=use_kernels
    )
    oracles = [_ScalarGroup(cfg.n_acceptors, cfg.n_instances) for _ in range(g)]
    alive = np.ones((g, cfg.n_acceptors), bool)
    if g > 1:
        mg.kill_acceptor(g - 1, 2)
        sh.kill_acceptor(g - 1, 2)
        alive[g - 1, 2] = False
    rng = np.random.default_rng(7)
    frozen = None
    rounds = 2 * cfg.n_instances // cfg.batch + 2   # wraps the ring twice
    for r in range(rounds):
        if g > 1 and r == 2:
            frozen = 0
            mg.freeze_group(frozen)
            sh.freeze_group(frozen)
        if frozen is not None and r == rounds - 3:
            back = sh.next_inst_host[frozen]
            mg.restore_group(frozen, back, 0)
            sh.restore_group(frozen, back, 0)
            frozen = None
        vals = rng.integers(-99, 99, (g, cfg.batch, cfg.value_words))
        vals = vals.astype(np.int32)
        act = np.ones((g, cfg.batch), bool)
        fresh_a, inst_a, val_a = mg.pipeline(vals, act)
        fresh_b, inst_b, val_b = sh.pipeline(vals, act)
        np.testing.assert_array_equal(fresh_a, fresh_b)
        np.testing.assert_array_equal(inst_a, inst_b)
        np.testing.assert_array_equal(val_a, val_b)
        for gid in range(g):
            if gid == frozen:
                assert not fresh_b[gid].any()   # inert: decides nothing
                continue
            decided = oracles[gid].round(vals[gid], alive[gid])
            for j, d in enumerate(decided):
                assert (d is not None) == bool(fresh_b[gid, j]), (gid, j)
                if d is not None:
                    assert d.inst == inst_b[gid, j]
                    np.testing.assert_array_equal(d.value, val_b[gid, j])
    for a, b in zip(_state_leaves(mg), _state_leaves(sh), strict=True):
        np.testing.assert_array_equal(a, b)
    # final register files agree with the scalar acceptors, per group
    h_rnd, h_vrnd = np.asarray(sh.stack.rnd), np.asarray(sh.stack.vrnd)
    for gid, oracle in enumerate(oracles):
        for aid, acc in enumerate(oracle.acceptors):
            for slot, (rnd, vrnd, _val) in acc.slots.items():
                assert h_rnd[gid, aid, slot] == rnd, (gid, aid, slot)
                assert h_vrnd[gid, aid, slot] == vrnd, (gid, aid, slot)


@pytest.mark.parametrize("use_kernels", [False, True])
def test_sharded_context_parity_with_failover(use_kernels):
    """A sharded context == the unsharded multi-group context (logs AND
    device registers) == G independent single-group contexts (logs), through
    a per-group coordinator failover and a dead acceptor elsewhere."""
    g = max(2, 2 * N_DEV)
    cfg = PaxosConfig(n_acceptors=3, n_instances=512, batch=16, n_groups=g)
    cfg1 = PaxosConfig(n_acceptors=3, n_instances=512, batch=16)
    mg = PaxosContext(cfg, use_kernels=use_kernels)
    sh = PaxosContext(cfg, use_kernels=use_kernels, mesh=make_group_mesh())
    singles = [
        PaxosContext(cfg1, use_kernels=use_kernels, fused=True)
        for _ in range(g)
    ]
    victim, casualty = 1, g - 1
    for ctx in (mg, sh):
        ctx.hw.kill_acceptor(casualty, 0)
    singles[casualty].hw.kill_acceptor(0)

    def wave(w):
        for gid in range(g):
            p = f"w{w}g{gid}".encode()
            mg.submit(p, group=gid)
            sh.submit(p, group=gid)
            singles[gid].submit(p)
        for ctx in (mg, sh, *singles):
            ctx.run_until_quiescent()

    for w in range(2):
        wave(w)
    mg.fail_coordinator(group=victim)
    sh.fail_coordinator(group=victim)
    singles[victim].fail_coordinator()
    for w in range(2, 4):
        wave(w)
    mg.restore_hardware_coordinator(group=victim)
    sh.restore_hardware_coordinator(group=victim)
    singles[victim].restore_hardware_coordinator()
    for w in range(4, 6):
        wave(w)

    assert sh.group_log == mg.group_log
    for gid in range(g):
        assert sh.group_log[gid] == singles[gid].delivered_log, gid
    for a, b in zip(_state_leaves(mg.hw), _state_leaves(sh.hw), strict=True):
        np.testing.assert_array_equal(a, b)
    assert all(len(log) == 6 for log in sh.group_log)


def test_placement_and_validation():
    cfg = PaxosConfig(n_acceptors=3, n_instances=128, batch=16, n_groups=4)
    sh = ShardedMultiGroupDataplane(cfg, mesh=make_group_mesh())
    gl = 4 // sh.n_shards
    assert sh.group_placement() == [gid // gl for gid in range(4)]
    assert [sh.shard_of_group(gid) for gid in range(4)] == sh.group_placement()
    with pytest.raises(ValueError):
        sh.shard_of_group(4)
    # G must tile the mesh axis exactly
    mesh = make_group_mesh()
    bad = PaxosConfig(n_groups=3 * mesh.shape["groups"] + 1)
    if bad.n_groups % mesh.shape["groups"]:
        with pytest.raises(ValueError):
            ShardedMultiGroupDataplane(bad, mesh=mesh)
    # a mesh without a groups axis is rejected
    with pytest.raises(ValueError):
        ShardedMultiGroupDataplane(cfg, mesh=jax.make_mesh((1,), ("data",)))


def test_sharded_g1_context_serves():
    """A sharded single-group context engages the group-keyed surface."""
    ctx = PaxosContext(
        PaxosConfig(n_acceptors=3, n_instances=128, batch=16),
        mesh=make_group_mesh(),
    )
    assert isinstance(ctx.hw, ShardedMultiGroupDataplane)
    for k in range(5):
        ctx.submit(f"x{k}".encode())
    ctx.run_until_quiescent()
    assert [p for _i, p in ctx.group_log[0]] == [
        f"x{k}".encode() for k in range(5)
    ]


def _run(code: str, devices: int = 8) -> str:
    env_code = (
        f"import os\n"
        f'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"\n'
    )
    proc = subprocess.run(
        [sys.executable, "-c", env_code + textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


@pytest.mark.slow
def test_sharded_multidevice():
    """8-shard mesh: G ∈ {8, 16} sharded == single-device unsharded, with
    the frozen group and the dead acceptor on distinct shards."""
    out = _run(
        """
        import numpy as np, jax
        from repro.core import MultiGroupDataplane, PaxosConfig, \\
            ShardedMultiGroupDataplane
        from repro.launch.mesh import make_group_mesh

        assert len(jax.devices()) == 8
        for use_k, g in ((False, 8), (False, 16), (True, 8)):
            cfg = PaxosConfig(n_acceptors=3, n_instances=128, batch=16,
                              n_groups=g)
            mg = MultiGroupDataplane(cfg, use_kernels=use_k)
            sh = ShardedMultiGroupDataplane(cfg, mesh=make_group_mesh(),
                                            use_kernels=use_k)
            assert sh.n_shards == 8
            frozen, casualty = 2, g - 1
            assert sh.shard_of_group(frozen) != sh.shard_of_group(casualty)
            rng = np.random.default_rng(3)
            mg.kill_acceptor(casualty, 1); sh.kill_acceptor(casualty, 1)
            mg.freeze_group(frozen); sh.freeze_group(frozen)
            for r in range(3):
                vals = rng.integers(-50, 50, (g, 16, cfg.value_words))
                vals = vals.astype(np.int32)
                act = np.ones((g, 16), bool)
                for x, y in zip(mg.pipeline(vals, act),
                                sh.pipeline(vals, act)):
                    np.testing.assert_array_equal(x, y)
            mg.restore_group(frozen, 0, 1); sh.restore_group(frozen, 0, 1)
            vals = rng.integers(-50, 50, (g, 16, cfg.value_words))
            vals = vals.astype(np.int32)
            act = np.ones((g, 16), bool)
            for x, y in zip(mg.pipeline(vals, act), sh.pipeline(vals, act)):
                np.testing.assert_array_equal(x, y)
            for x, y in zip(
                jax.tree_util.tree_leaves((mg.stack, mg.lstate)),
                jax.tree_util.tree_leaves((sh.stack, sh.lstate)),
            ):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            print("OK", use_k, g)
        print("SHARDED_OK")
        """
    )
    assert "SHARDED_OK" in out


_VOCABULARY_RUN = """
    import json, sys
    import numpy as np, jax
    sys.path.insert(0, "tests")
    from test_sharded_multigroup import _ScalarGroup
    from repro.core import (MultiGroupDataplane, PaxosConfig, PaxosContext,
                            ShardedMultiGroupDataplane)
    from repro.launch.mesh import make_group_mesh
    from repro.serve import ConsensusService

    assert len(jax.devices()) == 4
    # 16 groups per shard: packed at 1 and 8 lanes, full width past 8
    cfg = PaxosConfig(n_acceptors=3, n_instances=1024, batch=16, n_groups=64)
    out = {}

    # a seeded Zipf schedule through the served path, on a kernel context
    # (which compiles its vocabulary as it is built) and an unsharded jnp one
    ctx = PaxosContext(cfg, use_kernels=True, mesh=make_group_mesh())
    ref = PaxosContext(cfg, use_kernels=False)
    compiled, ran, pumping = [], [], [False]
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, _s, **kw: compiled.append(kw.get("fun_name"))
        if pumping[0] and ev == "/jax/core/compile/backend_compile_duration"
        else None)

    def record(dp, ran):
        # note the program of every dispatch ``dp`` runs from now on
        for name in ("_launch_packed", "_launch_full"):
            launch = getattr(dp, name)

            def recorded(prog, *a, _launch=launch):
                ran.append(prog)
                return _launch(prog, *a)

            setattr(dp, name, recorded)

    record(ctx.hw, ran)
    svc, rsvc = ConsensusService(ctx), ConsensusService(ref)
    rng = np.random.default_rng(2**31 + 2024)
    w = 1.0 / np.arange(1, 257) ** 0.99
    for wave, k in enumerate([1, 2, 3, 5, 9, 20, 60, 150, 400, 4, 33]):
        for i, r in enumerate(rng.choice(256, size=k, p=w / w.sum())):
            for s in (svc, rsvc):
                s.session(f"tenant{r}").submit(b"w%d.%d" % (wave, i))
        pumping[0] = True
        svc.pump()
        pumping[0] = False
        rsvc.pump()
    out["compiled"] = compiled
    out["n_programs"] = len(ctx.hw.programs)
    out["ran"] = sorted({(p.kind, p.width, p.burst) for p in ran})
    out["outside"] = [str(p) for p in ran if p not in ctx.hw.programs]
    out["logs_equal"] = ctx.group_log == ref.group_log
    out["delivered"] = sum(len(log) for log in ctx.group_log)
    out["state_equal"] = all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(
            jax.tree_util.tree_leaves((ctx.hw.stack, ctx.hw.lstate)),
            jax.tree_util.tree_leaves((ref.hw.stack, ref.hw.lstate))))

    # cohorts whose fullest shard holds 2 to 5 members run 8 packed lanes,
    # between the quantization steps 1 and 8: equal to the unsharded
    # dataplane and to the scalar roles, through a dead acceptor
    sh = ShardedMultiGroupDataplane(cfg, mesh=make_group_mesh(), use_kernels=True)
    mg = MultiGroupDataplane(cfg, use_kernels=False)
    oracles = [_ScalarGroup(3, cfg.n_instances) for _ in range(64)]
    alive = np.ones((64, 3), bool)
    for dp in (sh, mg):
        dp.kill_acceptor(17, 2)
    alive[17, 2] = False
    cohorts = [[0, 5, 9, 17], [2, 3, 4, 17, 40], [1, 6, 7, 11, 12, 20, 33],
               [9, 17, 18, 19, 50, 51], [0, 5, 9, 17]]
    widths, equal, sh_ran = [], True, []
    record(sh, sh_ran)
    for r, gids in enumerate(cohorts):
        be = (16, 8)[r % 2]
        vals = rng.integers(-99, 99, (len(gids), be, 16)).astype(np.int32)
        act = np.ones((len(gids), be), bool)
        got = sh.pipeline_cohort(gids, vals, act)
        want = mg.pipeline_cohort(gids, vals, act)
        widths.append(sh_ran[-1].width)
        equal &= all(np.array_equal(x, y) for x, y in zip(got, want))
        fresh, inst, value = got
        for row, gid in enumerate(gids):
            for j, d in enumerate(oracles[gid].round(vals[row], alive[gid])):
                equal &= (d is not None) == bool(fresh[row, j])
                if d is not None:
                    equal &= d.inst == inst[row, j]
                    equal &= np.array_equal(d.value, value[row, j])
    equal &= all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(
        jax.tree_util.tree_leaves((sh.stack, sh.lstate)),
        jax.tree_util.tree_leaves((mg.stack, mg.lstate))))
    out["parity_widths"] = widths
    out["parity_equal"] = bool(equal)
    print("VOCAB " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def vocabulary_run():
    out = _run(_VOCABULARY_RUN, devices=4)
    line = next(ln for ln in out.splitlines() if ln.startswith("VOCAB "))
    import json

    return json.loads(line[len("VOCAB "):])


def test_zipf_schedule_runs_only_listed_programs(vocabulary_run):
    """Every program a seeded Zipf schedule makes the sharded dispatch run
    is in ``programs``, the list ``core.plan.sharded_programs`` gives."""
    r = vocabulary_run
    assert r["outside"] == []
    assert r["n_programs"] == 8            # 2 bursts x (2 packed + 2 full)
    kinds = {(kind, width) for kind, width, _b in r["ran"]}
    assert {("packed", 1), ("packed", 8), ("full", 1)} <= kinds


def test_sharded_context_compiles_nothing_after_construction(vocabulary_run):
    """A sharded kernel context compiles its programs as it is built:
    pumping the schedule then compiles nothing, and the inert warm-up runs
    left the slabs as the unsharded context's."""
    r = vocabulary_run
    assert r["compiled"] == []
    assert r["logs_equal"] and r["delivered"] == 687
    assert r["state_equal"]


def test_packed_lanes_between_steps_match_unsharded_and_scalar_oracle(vocabulary_run):
    r = vocabulary_run
    assert r["parity_widths"] == [8, 8, 8, 8, 8]
    assert r["parity_equal"]
