"""Pallas TPU kernel: the digest that seals a snapshot (DESIGN.md §9).

``core.snapshot.SnapshotStore`` seals each group's drained ring prefix with
``tree_digest`` so that replicas at equal watermarks can compare snapshots by
one integer, and a transferred snapshot is checked before it is trusted.  The
digest must be (a) cheap — it folds every drained slot — and (b)
order-deterministic.

We use a weighted modular fold over the int32 bit pattern:

    digest = sum_i  bits(x_i) * (2*i + 1)   (mod 2^32)

(odd weights make the fold position-sensitive: permuted or shifted entries
collide with probability ~2^-32, unlike a plain sum).  The kernel is a
bandwidth-bound grid reduction: HBM-stream blocks into VMEM, fold in VREGs,
accumulate into a single scalar tile across grid steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
DEFAULT_BLOCK = 32 * 1024  # elements per grid step (128 KiB of int32)


def _digest_kernel(x_ref, out_ref):
    i = pl.program_id(0)
    rows = x_ref.shape[0]
    bits = x_ref[...]                                      # (rows, 128)
    idx = (
        (i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0))
        * LANES
        + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    )
    # fold whole (8, 128) tiles into the lane-parallel accumulator; the
    # 1024 partial sums are added on the host side of the kernel (int32
    # wraparound makes the split exact mod 2^32)
    part = (bits * (idx * 2 + 1)).reshape(rows // 8, 8, LANES).sum(axis=0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += part


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def digest(
    x: jax.Array, *, block: int = DEFAULT_BLOCK, interpret: bool = False
) -> jax.Array:
    """Fold a flat array into an int32 digest."""
    flat = x.reshape(-1)
    if flat.dtype != jnp.int32:
        flat = flat.view(jnp.int32)
    n = flat.shape[0]
    # lane-dense (rows, 128) view in whole (8, 128) tiles; zero padding adds
    # nothing to the fold
    tile = 8 * LANES
    nb = min(block, -(-n // tile) * tile)
    pad = (-n) % nb
    if pad:
        flat = jnp.pad(flat, (0, pad))
    rows = nb // LANES
    grid = ((n + pad) // nb,)
    out = pl.pallas_call(
        _digest_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.int32),
        interpret=interpret,
    )(flat.reshape(-1, LANES))
    return jnp.sum(out)


def tree_digest(tree, *, interpret: bool = False) -> jax.Array:
    """Digest a whole pytree (combines leaf digests order-sensitively)."""
    leaves = jax.tree_util.tree_leaves(tree)
    acc = jnp.int32(0)
    for leaf in leaves:
        d = digest(leaf, interpret=interpret)
        acc = acc * jnp.int32(1000003) + d  # polynomial combine
    return acc
