"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Shapes:

  * single-pod: (16, 16)    axes (data, model)   — 256 chips (one v5e pod)
  * multi-pod:  (2, 16, 16) axes (pod, data, model) — 512 chips / 2 pods

The ``pod`` axis maps onto DCN-connected pod boundaries: pure data
parallelism with hierarchical gradient reduction.  ``data`` is the FSDP axis
(intra-pod ICI), ``model`` the tensor/expert/sequence-parallel axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    # jax.make_mesh defaults to Explicit axes; every program here places
    # data with NamedSharding and shard_map, which want Auto axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(n_devices: int = 0, model_parallel: int = 1) -> jax.sharding.Mesh:
    """Small mesh over the locally available devices (tests / examples)."""
    n = n_devices or len(jax.devices())
    mp = model_parallel
    assert n % mp == 0
    return _auto_mesh((n // mp, mp), ("data", "model"))


def make_group_mesh(n_devices: int = 0) -> jax.sharding.Mesh:
    """1-D mesh with a single ``groups`` axis over the local devices.

    The placement domain of the groups-sharded consensus dataplane
    (``core.api.ShardedMultiGroupDataplane``, DESIGN.md §6): the G
    device-resident Paxos groups partition into contiguous slabs, one per
    mesh shard, so G scales with device count instead of one chip's
    VMEM/HBM.  On a single-device host this degenerates to a (1,) mesh and
    the sharded dataplane reduces bit-exactly to ``MultiGroupDataplane``.

    Capacity planning under dynamic membership (DESIGN.md §7): G is the
    *capacity* of the group axis, fixed at mesh/dataplane construction and
    divisible by the axis size.  Tenants create/retire over a free-list
    *within* that capacity — membership events flip replicated host scalars
    and never re-shard or move slab state — so size G for peak concurrent
    tenancy, not current tenancy.
    """
    n = n_devices or len(jax.devices())
    return _auto_mesh((n,), ("groups",))
