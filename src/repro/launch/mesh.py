"""The device mesh of the groups-sharded consensus dataplane.

A function (not a module-level constant) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    # jax.make_mesh defaults to Explicit axes; every program here places
    # data with NamedSharding and shard_map, which want Auto axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


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
