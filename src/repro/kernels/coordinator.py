"""Pallas TPU kernel: the CAANS coordinator (monotonic sequencer).

The paper's coordinator is a one-register P4 stage: bind each proposal to
``inst = next_inst++`` and stamp the coordinator round (header rewrite, no
packet synthesis).  Batched: ``inst = next_inst + iota(B)``; the new sequencer
watermark is ``next_inst + B``.  Trivial compute — the kernel exists because
the coordinator is a measured dataplane component in the paper (Table 1) and
because on TPU it fuses the header rewrite of a whole burst into one VMEM
pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import MSG_NOP, MSG_P2A

NO_ROUND = -1
DEFAULT_BLOCK_B = 128


def _coordinator_kernel(
    next_inst_ref,    # int32[1] scalar prefetch
    crnd_ref,         # int32[1] scalar prefetch
    active_ref,       # int32[1, BB]
    msgtype_ref,      # int32[1, BB] out
    inst_ref,         # int32[1, BB] out
    rnd_ref,          # int32[1, BB] out
    vrnd_ref,         # int32[1, BB] out
):
    i = pl.program_id(0)
    bb = active_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bb), 1)
    active = active_ref[...] != 0
    msgtype_ref[...] = jnp.where(active, MSG_P2A, MSG_NOP).astype(jnp.int32)
    inst_ref[...] = next_inst_ref[0] + i * bb + lane
    rnd_ref[...] = jnp.full((1, bb), crnd_ref[0], jnp.int32)
    vrnd_ref[...] = jnp.full((1, bb), NO_ROUND, jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def coordinator_sequence_window(
    next_inst: jax.Array,   # int32[]
    crnd: jax.Array,        # int32[]
    active: jax.Array,      # bool/int32[B]
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (msgtype[B], inst[B], rnd[B], vrnd[B], new_next_inst[])."""
    b = active.shape[0]
    bb = min(block_b, b)
    assert b % bb == 0
    grid = (b // bb,)

    # the burst rides as (1, B): a one-row block of a 2-D array is legal at
    # any B, a 1-D block is not (XLA tiles s32[B] by 1024)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bb), lambda i, *_: (0, i))],
        out_specs=[
            pl.BlockSpec((1, bb), lambda i, *_: (0, i)),
            pl.BlockSpec((1, bb), lambda i, *_: (0, i)),
            pl.BlockSpec((1, bb), lambda i, *_: (0, i)),
            pl.BlockSpec((1, bb), lambda i, *_: (0, i)),
        ],
    )
    out_shapes = [jax.ShapeDtypeStruct((1, b), jnp.int32) for _ in range(4)]
    fn = pl.pallas_call(
        _coordinator_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )
    ni = jnp.asarray(next_inst, jnp.int32).reshape((1,))
    cr = jnp.asarray(crnd, jnp.int32).reshape((1,))
    msgtype, inst, rnd, vrnd = (
        x.reshape((b,)) for x in fn(ni, cr, active.astype(jnp.int32).reshape(1, b))
    )
    return msgtype, inst, rnd, vrnd, (ni[0] + b).astype(jnp.int32)
