"""Compile the wire-path kernels for a described TPU v5e at deployment shapes.

The TPU compiler is installed even where no chip is attached: it refuses
what the Pallas interpreter accepts — blocks that break the (8, 128) tiling,
vector layouts Mosaic cannot lower, more VMEM than a kernel may use.  Each
test compiles one kernel at the paper's ring (N = 65536) and value width
(V = 16) for one chip of a ``v5e:2x2`` topology.  Nothing runs, so these
tests say nothing about results or speed; the interpret-mode parity suites
cover results.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import batched, fabric, plan
from repro.core.types import AcceptorState, CoordinatorState
from repro.kernels import coordinator, digest, wirepath

N, V = 65536, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*dims: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)

    return make


def _compile(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static
    ).compile()


def _multigroup_state(shape, g: int, a: int):
    return (
        shape(g, a, N), shape(g, a, N), shape(g, a, N, V),
        shape(g, N), shape(g, N), shape(g, N, V),
    )


@pytest.mark.parametrize(
    "g,gb,a,b,nblk",
    [
        (8, 1, 3, 128, 2),     # one group per step, window off the block edge
        (8, 1, 5, 8, 1),       # a sub-block burst: lanes masked in one block
        (64, 64, 3, 128, 1),   # the widest fold the planner allows
        (64, 64, 5, 128, 1),
        (64, 32, 7, 128, 1),   # more acceptors: the planner folds fewer groups
    ],
)
def test_cohort_round_compiles(shape, g, gb, a, b, nblk):
    assert gb <= plan.fold_cap(g, N, a, V)
    nb = g // gb
    _compile(
        wirepath.cohort_wirepath_round,
        shape(nb), shape(g), shape(g), shape(), shape(g, a),
        *_multigroup_state(shape, g, a), shape(g, b, V), shape(g), shape(g),
        group_block=gb, window_blocks=nblk,
    )


def test_persistent_wave_compiles(shape):
    g, a, k, b = 8, 3, 8, 128
    _compile(
        wirepath.persistent_wirepath_round,
        shape(g), shape(k, g), shape(k, g), shape(g), shape(), shape(g, a),
        *_multigroup_state(shape, g, a), shape(k, g, b, V), shape(g),
        group_block=1,
    )


@pytest.mark.parametrize("a,b", [(3, 1024), (5, 8192)])
def test_single_group_round_compiles(shape, a, b):
    _compile(
        wirepath.wirepath_round,
        shape(), shape(), shape(), shape(a),
        shape(a, N), shape(a, N), shape(a, N, V), shape(N), shape(N),
        shape(N, V), shape(b, V), shape(),
        window_blocks=plan.window_blocks(N, [0], b),
    )


def test_packed_shard_round_compiles(shape):
    c, gl, a, b = 4, 8, 3, 128
    _compile(
        wirepath.packed_shard_round,
        shape(c), shape(c), shape(c), shape(), shape(c, a),
        *_multigroup_state(shape, gl, a), shape(c, b, V), shape(c), shape(c),
        window_blocks=2,
    )


@pytest.mark.parametrize("a,b", [(3, 1024), (5, 8192)])
def test_vote_window_compiles(shape, a, b):
    _compile(
        wirepath.acceptor_vote_all_window,
        shape(a, N), shape(a, N), shape(a, N, V), shape(), shape(a),
        shape(b), shape(b), shape(b, V),
    )


@pytest.mark.parametrize("b", [128, 1024, 8192])
def test_coordinator_window_compiles(shape, b):
    _compile(coordinator.coordinator_sequence_window, shape(), shape(), shape(b))


@pytest.mark.parametrize("dims", [(1000, 16), (64, N, V)])
def test_digest_compiles(shape, dims):
    _compile(digest.digest, shape(*dims))


@pytest.mark.parametrize("b", [8, 128])
def test_jnp_engine_fits_one_chip(shape, b):
    """The jnp oracle is the reference the chip runs are checked against
    and the engine of recovery rounds: at 64 groups its round must fit one
    chip's HBM beside the state, with temporaries well under a quarter of
    the chip (a relayout of the padded value ring once took 16 GiB)."""
    g, a = 64, 3
    state = _multigroup_state(shape, g, a)
    compiled = jax.jit(
        batched.multigroup_fused_round, donate_argnums=(1, 2)
    ).lower(
        CoordinatorState(shape(g), shape(g)),
        AcceptorState(*state[:3]), batched.LearnerState(*state[3:]),
        shape(g, b, V), shape(g, b).update(dtype=jnp.bool_),
        shape(g, a).update(dtype=jnp.bool_), 2, reclaim_limit=shape(g),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


@pytest.mark.parametrize("kind,width,b", [("packed", 64, 128), ("full", 64, 128),
                                          ("full", 1, 8)])
def test_sharded_program_compiles_for_four_chips(topo, monkeypatch, kind, width, b):
    """The four-chip tenants deployment's programs at their real size:
    1,024 groups over a 2x2 mesh, 256 per chip.  Each compiles, updates
    the donated slabs in place, and needs little beside them."""
    import numpy as np
    from jax.sharding import AxisType, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "INTERPRET", False)
    mesh = jax.sharding.Mesh(
        np.array(topo.devices), ("groups",), axis_types=(AxisType.Auto,)
    )
    g, a, s = 1024, 3, 4
    slab, rep = NamedSharding(mesh, P("groups")), NamedSharding(mesh, P())

    def arg(*dims, sharding=rep, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    stack = AcceptorState(arg(g, a, N, sharding=slab), arg(g, a, N, sharding=slab),
                          arg(g, a, N, V, sharding=slab))
    lstate = batched.LearnerState(arg(g, N, sharding=slab), arg(g, N, sharding=slab),
                                  arg(g, N, V, sharding=slab))
    nblk = plan.sharded_window_blocks(N, b)
    if kind == "packed":
        fn = fabric.make_packed_sharded_round(
            mesh, quorum=2, use_kernels=True, window_blocks=nblk)
        c = width
        lowered = fn.lower(arg(s, c), arg(s, c), arg(s, c), arg(s, c), arg(s, c, a),
                           stack, lstate, arg(s, c, b, V, sharding=slab),
                           reclaim_limit=arg(s, c))
    else:
        fn = fabric.make_sharded_multigroup_round(
            mesh, n_groups=g, quorum=2, use_kernels=True, group_block=width,
            window_blocks=nblk)
        lowered = fn.lower(arg(g), arg(g), arg(g), arg(g, a), stack, lstate,
                           arg(g, b, V, sharding=slab),
                           arg(g, b, sharding=slab, dtype=jnp.bool_),
                           reclaim_limit=arg(g))
    # the benchmark finds the program on the device trace by this name
    assert "sharded" in lowered.as_text().split("\n", 1)[0]
    mem = lowered.compile().memory_analysis()
    per_chip = g // s * (a * (2 + V) + 2 + V) * N * 4
    assert mem.alias_size_in_bytes == per_chip
    assert mem.temp_size_in_bytes < 2**30
