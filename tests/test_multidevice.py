"""Multi-device integration tests, run in subprocesses with
--xla_force_host_platform_device_count=8 (the main test process must keep the
default single device for the smoke tests)."""
from __future__ import annotations

import subprocess
import sys
import textwrap

import pytest

# the subprocess tests are marked slow: skipped in the fast lane
slow = pytest.mark.slow


def test_mesh_construction_contract():
    """``make_group_mesh`` builds the groups-sharded dataplane's 1-D mesh:
    one ``groups`` axis over the local devices, with Auto axis types
    (``jax.make_mesh`` defaults to Explicit, which breaks the sharded
    paths).  Needs no devices beyond this process's own."""
    import jax
    from jax.sharding import AxisType

    from repro.launch.mesh import make_group_mesh

    for mesh in (make_group_mesh(), make_group_mesh(len(jax.devices()))):
        assert mesh.axis_names == ("groups",)
        assert dict(mesh.shape) == {"groups": len(jax.devices())}
        assert tuple(mesh.axis_types) == (AxisType.Auto,)


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


@slow
def test_fabric_consensus_round_all_devices_agree():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.fabric import make_fabric_consensus
        mesh = jax.make_mesh((8,), ("acc",))
        init_fn, step = make_fabric_consensus(mesh, axis="acc", n_instances=256,
                                              value_words=4)
        astate, cstate = init_fn()
        values = jnp.arange(8 * 2 * 4, dtype=jnp.int32).reshape(16, 4)
        active = jnp.ones((16,), bool)
        alive = jnp.ones((8,), bool)
        astate, cstate, decided, inst, value = step(astate, cstate, values, active, alive)
        assert np.asarray(decided).all(), decided
        np.testing.assert_array_equal(np.asarray(inst), np.arange(16))
        np.testing.assert_array_equal(np.asarray(value), np.asarray(values))
        assert int(cstate.next_inst) == 16
        # second round continues the instance window
        astate, cstate, decided, inst, _ = step(astate, cstate, values, active, alive)
        assert np.asarray(inst)[0] == 16
        print("FABRIC_OK")
        """
    )
    assert "FABRIC_OK" in out


@slow
def test_fabric_consensus_tolerates_f_failures():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.fabric import make_fabric_consensus
        mesh = jax.make_mesh((8,), ("acc",))
        # quorum 5 of 8 -> tolerate 3 dead acceptors
        init_fn, step = make_fabric_consensus(mesh, axis="acc", quorum=5,
                                              n_instances=128, value_words=2)
        astate, cstate = init_fn()
        values = jnp.ones((8, 2), jnp.int32)
        active = jnp.ones((8,), bool)
        alive = jnp.asarray([True]*5 + [False]*3)
        astate, cstate, decided, inst, value = step(astate, cstate, values, active, alive)
        assert np.asarray(decided).all()
        # 4 alive < quorum 5 -> no decision
        alive = jnp.asarray([True]*4 + [False]*4)
        astate, cstate, decided, *_ = step(astate, cstate, values, active, alive)
        assert not np.asarray(decided).any()
        print("QUORUM_OK")
        """
    )
    assert "QUORUM_OK" in out


@slow
def test_packed_shard_dispatch_matches_full_width():
    """Packed segment-id cohort dispatch == full-width sharded dispatch,
    bit-for-bit (registers AND outputs), on a real 2-shard mesh with a
    ragged cohort (2 lanes on shard 0, 1 lane + 1 pad on shard 1) and a
    dead acceptor — both engines."""
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import batched, fabric
        from repro.core.plan import NO_ROUND, NOP_SENTINEL
        from repro.launch.mesh import make_group_mesh

        rng = np.random.default_rng(0)
        G, A, N, V, B = 8, 3, 256, 2, 16      # 2 shards x Gl = 4
        mesh = make_group_mesh()
        assert len(jax.devices()) == 2
        gl = G // 2
        _cs, stack, lstate = batched.init_multigroup_state(G, A, N, V)

        # prime every ring with 2 full-width rounds
        full = fabric.make_sharded_multigroup_round(
            mesh, n_groups=G, quorum=2, use_kernels=False)
        ni = jnp.zeros((G,), jnp.int32)
        cr = jnp.full((G,), 7, jnp.int32)
        en = jnp.ones((G,), jnp.int32)
        alive = jnp.ones((G, A), jnp.int32)
        for _ in range(2):
            vals = jnp.asarray(rng.integers(0, 100, (G, B, V)), jnp.int32)
            stack, lstate, *_ = full(ni, cr, en, alive, stack, lstate,
                                     vals, jnp.ones((G, B), bool))
            ni = ni + B
        stack0 = jax.tree_util.tree_map(np.asarray, stack)
        lstate0 = jax.tree_util.tree_map(np.asarray, lstate)
        ni0 = np.asarray(ni)

        # ragged cohort [1, 2, 6]: shard 0 lanes {1, 2}, shard 1 lane {6}+pad
        gids = [1, 2, 6]
        C = 2
        seg = np.zeros((2, C), np.int32); enp = np.zeros((2, C), np.int32)
        nip = np.zeros((2, C), np.int32)
        crp = np.full((2, C), NO_ROUND, np.int32)
        alp = np.ones((2, C, A), np.int32)
        valsp = np.full((2, C, B, V), NOP_SENTINEL, np.int32)
        cohort_vals = rng.integers(0, 100, (len(gids), B, V)).astype(np.int32)
        lanes = {0: [], 1: []}
        for i, g in enumerate(gids):
            s = g // gl
            j = len(lanes[s]); lanes[s].append(g)
            seg[s, j] = g % gl; enp[s, j] = 1
            nip[s, j] = ni0[g]; crp[s, j] = 7
            valsp[s, j] = cohort_vals[i]
        alp[0, 1, 0] = 0                      # dead acceptor on group 2
        alive_full = np.ones((G, A), np.int32); alive_full[2, 0] = 0

        # reference: full-width dispatch with only the cohort enabled
        en_r = np.zeros((G,), np.int32)
        cr_r = np.full((G,), NO_ROUND, np.int32)
        vals_r = np.full((G, B, V), NOP_SENTINEL, np.int32)
        for i, g in enumerate(gids):
            en_r[g] = 1; cr_r[g] = 7; vals_r[g] = cohort_vals[i]
        st = jax.tree_util.tree_map(jnp.asarray, stack0)
        ls = jax.tree_util.tree_map(jnp.asarray, lstate0)
        st, ls, fresh_r, _i, win_r, val_r = full(
            jnp.asarray(ni0), jnp.asarray(cr_r), jnp.asarray(en_r),
            jnp.asarray(alive_full), st, ls, jnp.asarray(vals_r),
            jnp.ones((G, B), bool))
        ref = (jax.tree_util.tree_map(np.asarray, st),
               jax.tree_util.tree_map(np.asarray, ls),
               np.asarray(fresh_r), np.asarray(win_r), np.asarray(val_r))

        for use_k in (False, True):
            packed = fabric.make_packed_sharded_round(
                mesh, quorum=2, use_kernels=use_k)
            st = jax.tree_util.tree_map(jnp.asarray, stack0)
            ls = jax.tree_util.tree_map(jnp.asarray, lstate0)
            st, ls, fresh, _i, win, val = packed(
                jnp.asarray(seg), jnp.asarray(nip), jnp.asarray(crp),
                jnp.asarray(enp), jnp.asarray(alp), st, ls,
                jnp.asarray(valsp))
            got_st = jax.tree_util.tree_map(np.asarray, st)
            got_ls = jax.tree_util.tree_map(np.asarray, ls)
            for a, b in zip(jax.tree_util.tree_leaves((got_st, got_ls)),
                            jax.tree_util.tree_leaves((ref[0], ref[1]))):
                np.testing.assert_array_equal(a, b)
            fresh = np.asarray(fresh).reshape(2, C, B)
            win = np.asarray(win).reshape(2, C, B)
            val = np.asarray(val).reshape(2, C, B, V)
            for g in gids:
                s, j = g // gl, lanes[g // gl].index(g)
                np.testing.assert_array_equal(fresh[s, j], ref[2][g])
                np.testing.assert_array_equal(win[s, j], ref[3][g])
                np.testing.assert_array_equal(val[s, j], ref[4][g])
        print("PACKED_OK")
        """,
        devices=2,
    )
    assert "PACKED_OK" in out


@slow
def test_live_migration_across_shards_matches_twins():
    """End-to-end live slab migration on a real 2-shard mesh: skewed load,
    a retire on the destination shard, then migrating the hot tenant from
    shard 0 to shard 1 without stopping the service — decided payload
    streams must keep matching per-group twins on both engines, and the
    placement map must record the move."""
    out = _run(
        """
        import numpy as np
        from repro.core.api import PaxosContext, ShardedMultiGroupDataplane
        from repro.core.types import PaxosConfig
        from repro.launch.mesh import make_group_mesh

        def run(use_kernels):
            cfg = PaxosConfig(n_groups=4, n_acceptors=3, n_instances=256,
                              batch=16, value_words=4)
            cfg1 = PaxosConfig(n_groups=1, n_acceptors=3, n_instances=256,
                               batch=16, value_words=4)
            ctx = PaxosContext(cfg, mesh=make_group_mesh(),
                               use_kernels=use_kernels, snapshots=True)
            twins = [PaxosContext(cfg1, use_kernels=use_kernels, fused=True,
                                  snapshots=True) for _ in range(4)]
            rng = np.random.default_rng(1)

            def waves(n, groups, hot=0):
                for w in range(n):
                    for g in groups:
                        k = 12 if g == hot else (2 if w % 2 == 0 else 1)
                        for _ in range(k):
                            p = bytes(rng.integers(0, 255, 6).astype(np.uint8))
                            ctx.submit(p, group=g)
                            twins[g].submit(p, group=0)
                    ctx.run_until_quiescent()
                    for g in groups:
                        twins[g].run_until_quiescent()

            waves(4, [0, 1, 2, 3])
            hw = ctx.hw
            assert isinstance(hw, ShardedMultiGroupDataplane)
            assert hw.placement.identity_map()
            ctx.retire_group(3)               # vacate a slot on shard 1
            assert hw.shard_of_group(0) == 0
            ctx.migrate_group(0, 1)           # live: drain/seal/swap/restore
            assert hw.shard_of_group(0) == 1, hw.group_placement()
            waves(3, [0, 1, 2])               # keep serving after the move
            for g in (0, 1, 2):
                a = [p for _i, p in ctx.full_group_log(g)]
                b = [p for _i, p in twins[g].full_group_log(0)]
                assert a == b, (use_kernels, g, len(a), len(b))
            print("MIGRATE_OK", use_kernels)

        run(False)
        run(True)
        """,
        devices=2,
    )
    assert out.count("MIGRATE_OK") == 2
