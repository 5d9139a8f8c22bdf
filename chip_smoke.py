#!/usr/bin/env python3
"""Drive the consensus main path once on a TPU and check what it decides.

    python chip_smoke.py             # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

Everything runs in this one process, through the entry points a user calls
(``PaxosContext`` -> ``ConsensusService``/``Session`` -> ``ReplicatedKV``),
with the Pallas wire path as the default engine on a TPU:

  (a) single-group replicated KV at the paper's shape (A=3, N=65536,
      64-byte values, snapshots on): load, then a get/put mix; partway an
      acceptor dies, the coordinator fails over and comes back, and one
      snapshot is sealed.  Every acknowledged put reads back, the KV state
      equals a dict reference, the digest kernel's seal equals the plain
      jnp digest, and the decided log is bit-identical to the same traffic
      on the jnp engine.
  (b) multi-tenant on one chip: G=64 groups under Zipf-skewed sessions, so
      cohort tiers and the persistent wave kernel both run; every group
      log is bit-identical to the jnp engine on the same traffic.
  (c) ``--chips 4``: G=256 groups sharded over four chips, the same skewed
      traffic and one live migration across shards, against the unsharded
      dataplane on the same traffic; the slab state must span all four chips
      in balance.

Each phase prints its counts (ops, decided instances, kernel and jnp
dispatches, compiles) and wall time — set-up included, so not a benchmark.
Any failure raises.  Without a TPU, or without the repository's ``src/``
next to this file, it exits non-zero and prints no result.  The last line
is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The deployment's scale; ``PAPER`` is what the smoke test runs."""

    n_instances: int = 65536
    kv_keys: int = 20000
    kv_mixed_ops: int = 10000
    kv_sessions: int = 16
    tenants: int = 64
    tenant_sessions: int = 2048
    tenant_ops: int = 12000      # per wave
    tenant_waves: int = 3
    shards: int = 4


PAPER = Sizes()
A, V, ZIPF_S = 3, 16, 1.1

_compiles = [0]


def _count_compiles(event: str, _secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles[0] += 1


class Phase:
    """Prints one line per phase: its counts, compiles and wall time."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> dict:
        self.t0 = time.perf_counter()
        self.c0 = _compiles[0]
        self.stats: dict = {}
        return self.stats

    def __exit__(self, exc_type, *_exc) -> None:
        if exc_type is None:
            self.stats["compiles"] = _compiles[0] - self.c0
            self.stats["wall_s"] = round(time.perf_counter() - self.t0, 2)
            print(f"phase {self.name}: {json.dumps(self.stats)}", flush=True)


def _dispatches(hw) -> dict:
    return {
        "kernel_dispatches": hw.dispatch_count - hw.jnp_dispatch_count,
        "jnp_dispatches": hw.jnp_dispatch_count,
        "persistent_dispatches": hw.persistent_dispatch_count,
    }


def _ref_seal(insts, values) -> int:
    """A snapshot seal by the plain jnp digest (``kernels.ref.digest``),
    folded as ``kernels.digest.tree_digest`` folds its leaves."""
    import jax.numpy as jnp

    from repro.kernels import ref

    acc = jnp.int32(0)
    for leaf in (insts, values):
        acc = acc * jnp.int32(1000003) + ref.digest(jnp.asarray(leaf))
    return int(acc)


def _log_digest(logs) -> str:
    import hashlib

    h = hashlib.sha256()
    for log in logs:
        for inst, payload in log:
            h.update(inst.to_bytes(8, "little", signed=True) + payload)
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# (a) single-group replicated KV
# ---------------------------------------------------------------------------
def kv_run(sizes: Sizes, use_kernels: bool, seed: int) -> dict:
    """Load ``kv_keys`` keys, then ``kv_mixed_ops`` ops, half puts and half
    gets; halfway through the mix an acceptor dies, the coordinator fails
    over and is restored, and group 0 is snapshotted.  Checks reads and
    the final state against a dict; returns the decided log and counts."""
    import numpy as np

    from repro.core import PaxosConfig, PaxosContext
    from repro.serve import ConsensusService, ReplicatedKV

    cfg = PaxosConfig(n_acceptors=A, n_instances=sizes.n_instances, value_words=V)
    ctx = PaxosContext(cfg, fused=True, snapshots=True, use_kernels=use_kernels)
    svc = ConsensusService(ctx)
    kv = ReplicatedKV(svc)
    rng = np.random.default_rng(seed)
    sessions = [kv.session(f"client{i}") for i in range(sizes.kv_sessions)]
    keys = [b"k%07d" % i for i in range(sizes.kv_keys)]
    ref: dict[bytes, bytes] = {}

    def put_round(idx) -> None:
        pending = {}
        for n, i in enumerate(idx):
            value = rng.bytes(24)
            sessions[n % len(sessions)].put(keys[i], value)
            pending[keys[i]] = value
        svc.run_until_quiescent()
        assert ctx.quiescent(), "puts still in flight after quiescence"
        ref.update(pending)            # decided = acknowledged

    def check_reads(idx) -> int:
        for n, i in enumerate(idx):
            got = sessions[(n + 1) % len(sessions)].get(keys[i])
            assert got == ref.get(keys[i]), (keys[i], got, ref.get(keys[i]))
        return len(idx)

    for lo in range(0, sizes.kv_keys, 2000):
        put_round(range(lo, min(lo + 2000, sizes.kv_keys)))
    recovery_jnp = 0
    rounds, per = 10, sizes.kv_mixed_ops // 20
    gets = 0
    for r in range(rounds):
        if r == rounds // 2:
            before = ctx.hw.jnp_dispatch_count
            ctx.hw.kill_acceptor(A - 1)
            ctx.fail_coordinator()
            ctx.restore_hardware_coordinator()
            recovery_jnp = ctx.hw.jnp_dispatch_count - before
            snap = ctx.snapshot_group(0)
        put_round(rng.integers(0, sizes.kv_keys, per))
        gets += check_reads(rng.integers(0, sizes.kv_keys, per))
    # every acknowledged put reads back, and the replica equals the dict
    gets += check_reads(range(sizes.kv_keys))
    kv.refresh()
    state = {k: v for k, (v, _ver) in kv.replica(0).state.items()}
    assert state == ref, "replica state differs from the dict reference"
    log = ctx.full_group_log(0)
    stats = _dispatches(ctx.hw)
    stats["jnp_dispatches"] -= recovery_jnp
    return {
        "log": log,
        "seal": snap.seal,
        "seal_ref": _ref_seal(snap.insts, snap.values),
        "ops": sizes.kv_keys + rounds * per + gets,
        "decided": len(log),
        "recovery_jnp_dispatches": recovery_jnp,
        **stats,
    }


def phase_kv(sizes: Sizes) -> None:
    import gc

    with Phase("a.kv_pallas") as st:
        got = kv_run(sizes, use_kernels=True, seed=1)
        st.update({k: v for k, v in got.items() if k not in ("log", "seal", "seal_ref")})
        assert got["jnp_dispatches"] == 0, "steady KV rounds left the kernel path"
        assert got["seal"] == got["seal_ref"], "digest kernel differs from jnp"
        assert got["kernel_dispatches"] > 0
        gc.collect()                   # the KV sessions and replica form cycles
    with Phase("a.kv_jnp_reference") as st:
        want = kv_run(sizes, use_kernels=False, seed=1)
        st.update({k: v for k, v in want.items() if k not in ("log", "seal", "seal_ref")})
        assert want["seal"] == want["seal_ref"]
    with Phase("a.kv_compare") as st:
        assert got["log"] == want["log"], "decided logs differ from the jnp engine"
        assert got["seal"] == want["seal"], "snapshot seals differ"
        st.update(log_entries=len(got["log"]), log_sha=_log_digest([got["log"]]))


# ---------------------------------------------------------------------------
# (b) multi-tenant on one chip, (c) sharded over four
# ---------------------------------------------------------------------------
def tenant_run(
    sizes: Sizes, n_groups: int, use_kernels: bool, seed: int,
    mesh=None, move=None,
) -> dict:
    """Zipf-skewed sessions over ``n_groups`` groups, ``tenant_waves``
    waves, pumped to quiescence after each.  With ``move`` a cold group
    retires after the first wave and the hottest group moves to the retired
    group's shard: ``move=True`` picks them from the sharded placement,
    a ``(hot, cold, dst)`` tuple replays an earlier pick.  An unsharded
    context runs the move's drain (``snapshot_group``) in place of the
    migration, so both decide the same.  Returns every group's stitched
    log and counts."""
    import numpy as np

    from repro.core import PaxosConfig, PaxosContext
    from repro.serve import ConsensusService

    cfg = PaxosConfig(
        n_acceptors=A, n_instances=sizes.n_instances, value_words=V,
        n_groups=n_groups,
    )
    ctx = PaxosContext(cfg, use_kernels=use_kernels, mesh=mesh, snapshots=True)
    svc = ConsensusService(ctx)
    rng = np.random.default_rng(seed)
    weights = 1.0 / np.arange(1, sizes.tenant_sessions + 1) ** ZIPF_S
    weights /= weights.sum()
    moved = None
    for wave in range(sizes.tenant_waves):
        picks = rng.choice(sizes.tenant_sessions, sizes.tenant_ops, p=weights)
        for j, s in enumerate(picks):
            svc.session(f"tenant{s}").submit(b"w%d:s%d:%d" % (wave, s, j))
        svc.run_until_quiescent()
        assert ctx.quiescent(), "tenant traffic still in flight"
        if move and wave == 0:
            if move is True:
                loads = svc.group_loads()
                hot = int(np.argmax(loads))
                shard_of = svc.group_placement()
                dst = (shard_of[hot] + 1) % sizes.shards
                cold = min(
                    (g for g in range(n_groups) if shard_of[g] == dst),
                    key=lambda g: (loads[g], g),
                )
            else:
                hot, cold, dst = move
            svc.retire_group(cold)
            if mesh is not None:
                svc.migrate_group(hot, dst)
                assert ctx.hw.shard_of_group(hot) == dst
            else:
                ctx.snapshot_group(hot)
            moved = (hot, cold, dst)
    report = ctx.planner.report()
    return {
        "ctx": ctx,
        "logs": [ctx.full_group_log(g) for g in range(n_groups)],
        "ops": sizes.tenant_ops * sizes.tenant_waves,
        "persistent_waves": report["persistent_waves"],
        "burst_shapes": report["burst_shapes"],
        "moved": moved,
        **_dispatches(ctx.hw),
    }


def _bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def _free(run: dict) -> None:
    import gc

    del run["ctx"]
    gc.collect()


def _compare_logs(got: list, want: list, what: str) -> dict:
    bad = [g for g, (x, y) in enumerate(zip(got, want, strict=True)) if x != y]
    assert not bad, f"group logs differ from {what} for groups {bad[:8]}"
    return {
        "decided": sum(len(x) for x in got),
        "log_sha": _log_digest(got),
    }


def phase_tenants(sizes: Sizes) -> None:
    fields = ("ops", "persistent_waves", "burst_shapes", "kernel_dispatches",
              "jnp_dispatches", "persistent_dispatches")
    with Phase("b.tenants_pallas") as st:
        got = tenant_run(sizes, sizes.tenants, use_kernels=True, seed=2)
        st.update({k: got[k] for k in fields})
        assert got["jnp_dispatches"] == 0, "steady rounds left the kernel path"
        assert got["persistent_dispatches"] > 0, "no persistent wave kernel ran"
        assert min(got["burst_shapes"]) < max(got["burst_shapes"]), (
            "no cold cohort tier ran"
        )
        got_logs = got.pop("logs")
        _free(got)                     # the slabs leave HBM before the reference
    with Phase("b.tenants_jnp_reference") as st:
        want = tenant_run(sizes, sizes.tenants, use_kernels=False, seed=2)
        st.update({k: want[k] for k in fields})
        want_logs = want.pop("logs")
        _free(want)
    with Phase("b.tenants_compare") as st:
        st.update(_compare_logs(got_logs, want_logs, "the jnp engine"))
        # both engines plan the same waves
        assert got["persistent_dispatches"] == want["persistent_dispatches"]


def phase_sharded(sizes: Sizes, chips: int) -> None:
    import jax

    from repro.launch.mesh import make_group_mesh

    g = sizes.tenants * chips
    fields = ("ops", "burst_shapes", "moved", "kernel_dispatches", "jnp_dispatches")
    with Phase(f"c.sharded_{chips}chips") as st:
        got = tenant_run(
            sizes, g, use_kernels=True, seed=3,
            mesh=make_group_mesh(chips), move=True,
        )
        st.update({k: got[k] for k in fields})
        assert got["jnp_dispatches"] == 0, "steady rounds left the kernel path"
        hw = got["ctx"].hw
        for x in jax.tree_util.tree_leaves((hw.stack, hw.lstate)):
            assert len(x.sharding.device_set) == chips, x.sharding
        used = [_bytes_in_use(d) for d in jax.devices()[:chips]]
        st["bytes_in_use"] = used
        assert max(used) <= 1.25 * min(used), f"slab state unbalanced: {used}"
        got_logs = got.pop("logs")
        del hw
        _free(got)
    with Phase("c.unsharded_reference") as st:
        want = tenant_run(sizes, g, use_kernels=True, seed=3, move=got["moved"])
        st.update({k: want[k] for k in fields})
        want_logs = want.pop("logs")
        _free(want)
    with Phase("c.sharded_compare") as st:
        st.update(_compare_logs(got_logs, want_logs, "the unsharded dataplane"))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4 runs only the sharded phase, across four chips",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, found {devices[0].platform}",
            file=sys.stderr,
        )
        return 1
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
            file=sys.stderr,
        )
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache(ROOT)}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_count_compiles)
    if args.chips == 1:
        phase_kv(PAPER)
        phase_tenants(PAPER)
    else:
        phase_sharded(PAPER, args.chips)
    d = jax.devices()[0]
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": d.platform,
            "kind": d.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
