"""Pallas TPU megakernel: the fused CAANS wire path, for G resident groups.

One ``pallas_call`` executes a *complete* Phase-2 round — coordinator
sequencing, the Phase-2 vote of all ``A = 2f+1`` acceptors against the
stacked instance rings, the learner quorum count, and the ``LearnerState``
ring-dedup update — for **G independent Paxos groups at once**.  This is the
TPU analogue of the paper's core claim (a consensus round costs barely more
than forwarding the packets) combined with NetChain's scale-free observation:
a device pipeline serves *many* replicated groups as one shared service
(PAPER.md; DESIGN.md §3, §5).

Layout (DESIGN.md §5):

    grid = (G // GB, NBLK)          # group axis x ring blocks of the window
    acceptor rings (G, A, N)        --BlockSpec (GB, A, BB)-->     VMEM, in-place
    acceptor vals  (G, A, V, N)     --BlockSpec (GB, A, V, BB)-->  VMEM, in-place
    learner rings  (G, 1, N)        --BlockSpec (GB, 1, BB)-->     VMEM, in-place
    learner vals   (G, V, N)        --BlockSpec (GB, V, BB)-->     VMEM, in-place
    burst values   (G, V, NBLK*BB)  --BlockSpec (GB, V, BB)-->     VMEM
    fresh/win/value outputs         <--                            VMEM

The wrappers take the host layouts — ``(..., N, V)`` value words, ``(G, N)``
learner rings — and hand the kernels V-major views with a unit row axis
(``_v_major``).  ``BB = ring_block(N)`` is the 128-lane tile (the whole ring
when N is not a multiple of it), so every block is one the TPU can tile.  A window
``[base, base + B)`` may start anywhere and be any length up to N: the grid
visits the NBLK ring blocks that cover it, the wrapper moves the burst into
ring-lane order, and lanes outside the window ride through refused —
exactly like lanes past the reclamation limit.

Groups never interact: each has its own coordinator watermark/round (the
``next_inst``/``crnd`` scalar-prefetch vectors are per-group), its own
acceptor rings, its own learner ring, and its own liveness row in the
``(G, A)`` alive mask.  The quorum reduction runs down the acceptor axis
*within* each group block.

``group_block`` picks the group→grid mapping:

  * ``group_block=1`` (default): one group per grid step, each group's ring
    window derived from its own watermark — fully general, including groups
    whose watermarks diverged after a per-group coordinator failover.
  * ``group_block=GB>1``: GB groups ride the leading block dimension of a
    single grid step (the batch analogue of the acceptor-in-block decision).
    Requires the GB groups of a block to share one watermark ("lockstep"),
    since a block has a single ring offset.  This is the
    highest-amortization mapping for the common case of a service pumping
    all groups together.

Invariants (maintained by ``core.api.MultiGroupDataplane``, asserted where
shapes are static): ``GB | G``, and every enabled window covers at most the
NBLK blocks the grid visits without wrapping onto its own first block
(``base % BB + B <= NBLK * BB <= N``; ``core.plan.window_blocks``).  The
persistent K-round entry additionally needs block-aligned windows, so its
rounds never share a ring block.  Liveness is a *runtime* input —
the ``(G, A)`` alive mask rides in scalar-prefetch SMEM, so killing/reviving
an acceptor in any group never recompiles the kernel.

**Enabled mask (dynamic membership, DESIGN.md §7).**  ``enabled`` marks which
groups advance this round; a disabled group — frozen under a software
coordinator, vacant (retired from the free-list), or simply idle — rides
along *inert*: its round is presented as NO_ROUND (acceptors reject every
slot) and, under ``group_block > 1``, its watermark is substituted with the
block's enabled-lockstep base so a folded block keeps a single well-defined
ring offset even when disabled members' watermarks diverged.  The disabled
group's ring windows are loaded and stored back bit-unchanged, so folding
over vacant slots is state-exact.

**Cohort selection (DESIGN.md §8).**  ``cohort_wirepath_round`` is the
general entry: a ``gsel`` scalar-prefetch vector names which GB-aligned
group blocks the grid visits, so a dispatch costs what its cohort costs —
the group-axis analogue of the ring blocking.  Unselected groups' slabs
are never loaded; their rows of the aliased state outputs retain the input
data, exactly like unvisited ring blocks along the batch axis.  Each
selected block derives its ring offset from its own (substituted)
watermark base, which is what lets cohorts that diverged after per-group
failovers fold block-wise instead of collapsing to ``group_block = 1``.
``multigroup_wirepath_round`` is its every-block-selected slice; the host
side of the policy (burst tiers, fold widths, block selection) lives in
``core.plan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import MSG_NOP, MSG_P2A, MSG_P2B, MSG_REJECT

NO_ROUND = -1

# Ring slots per grid step; 128 is the int32 lane width.
DEFAULT_BLOCK_B = 128

# Scoped-VMEM limit of the wire-path kernels; ``core.plan.MAX_FOLD_LANES``
# keeps a grid step's blocks (GB groups x BB ring slots) inside it.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def ring_block(n: int, block_b: int = DEFAULT_BLOCK_B) -> int:
    """Ring slots per grid step for an ``n``-slot ring: ``block_b`` when it
    tiles the ring, else the whole ring (a block equal to the array's extent
    is always a legal TPU block)."""
    return block_b if n % block_b == 0 else n


def _default_window_blocks(n: int, bb: int, b: int) -> int:
    # enough blocks for a B-slot window at any offset inside its first block
    return min(n // bb, -(-b // bb) + 1)


# The kernels see every value-word array V-major, (..., V, N), and every
# learner ring with a unit row axis, (R, 1, N).  V-major is how XLA lays
# out the (..., N, V) slabs on a TPU (V=16 on sublanes, the ring on lanes),
# so the swap is free there, and the value words need no lane padding.  The
# unit row axis makes a one-group block legal (TPU blocks need their last
# two dims divisible by (8, 128) or equal to the array's).
def _v_major(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, -1, -2)


def _host_state(outs) -> tuple[jax.Array, ...]:
    """The six kernel state outputs in the host layouts again."""
    st_rnd, st_vrnd, st_val, ldel, linst, lval = outs[:6]
    return (
        st_rnd, st_vrnd, _v_major(st_val), ldel[:, 0], linst[:, 0],
        _v_major(lval),
    )


def _to_ring_lanes(values: jax.Array, off: jax.Array, lanes: int) -> jax.Array:
    """(C, B, V) burst rows -> (C, V, lanes) in ring-lane order: row r's
    burst slot j lands on lane ``off[r] + j``; other lanes carry zeros."""
    c, b, v = values.shape
    j = jnp.arange(lanes, dtype=jnp.int32)[None, None, :] - off[:, None, None]
    idx = jnp.broadcast_to(jnp.clip(j, 0, b - 1), (c, v, lanes))
    got = jnp.take_along_axis(_v_major(values), idx, axis=2)
    return jnp.where((j >= 0) & (j < b), got, 0)


def _from_ring_lanes(x: jax.Array, off: jax.Array, b: int) -> jax.Array:
    """Inverse of ``_to_ring_lanes`` for a (C, 1|V, lanes) output."""
    idx = jnp.arange(b, dtype=jnp.int32)[None, None, :] + off[:, None, None]
    idx = jnp.broadcast_to(idx, x.shape[:2] + (b,))
    return jnp.take_along_axis(x, idx, axis=2)


def _window_outputs(outs, off: jax.Array, b: int) -> tuple[jax.Array, ...]:
    """Kernel outputs -> ``(state..., fresh[C, B], win[C, B], value[C, B, V])``:
    host layouts again, per-lane outputs back in burst order."""
    fresh, win, value = (_from_ring_lanes(x, off, b) for x in outs[6:])
    return _host_state(outs) + (
        fresh[:, 0], win[:, 0], _v_major(value),
    )


def _alive_rows(alive_of, a: int, bb: int) -> jax.Array:
    """(A, BB) int32 liveness tile from A scalar-prefetch reads."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (a, bb), 0)
    mask = jnp.zeros((a, bb), jnp.int32)
    for j in range(a):
        mask = jnp.where(rows == j, alive_of(j), mask)
    return mask


# ---------------------------------------------------------------------------
# The fused multi-group round megakernel
# ---------------------------------------------------------------------------
def _phase2_rows(
    i,              # ring-block index of this grid step within the window
    quorum,         # int32 scalar (f+1)
    scalars,        # k -> (window base, round, alive_of(j), permit limit)
    values_ref,     # int32[GB, V, BB]     burst values, ring-lane order
    st_rnd_ref,     # int32[GB, A, BB]     acceptor ring blocks
    st_vrnd_ref,    # int32[GB, A, BB]
    st_val_ref,     # int32[GB, A, V, BB]
    ldel_ref,       # int32[GB, 1, BB]     learner ring blocks
    linst_ref,      # int32[GB, 1, BB]
    lval_ref,       # int32[GB, V, BB]
    o_rnd_ref, o_vrnd_ref, o_val_ref,      # acceptor ring blocks, updated
    o_ldel_ref, o_linst_ref, o_lval_ref,   # learner ring blocks, updated
    fresh_ref,      # int32[GB, 1, BB]     fresh (non-duplicate) delivery mask
    win_ref,        # int32[GB, 1, BB]     winning vrnd (NO_ROUND if none)
    value_ref,      # int32[GB, V, BB]     decided value
):
    """One Phase-2 round over one ring block of ``GB`` groups: sequence ->
    all-acceptor vote -> learner quorum -> ring dedup, one group row at a
    time.  Shared by every wire-path kernel body, which differ only in where
    a row's scalars come from — identical arithmetic is what makes the
    K-round entry bit-exact against K single rounds by construction.

    A block of one row runs the body once; a folded block runs it as a
    loop over the rows, indexing the blocks' leading (untiled) group
    dimension dynamically, so the traced kernel does not grow with GB.
    The acceptor loop (A is small and static) unrolls.

    Every per-slot quantity is a ``(1, BB)`` lane row, which broadcasts down
    the V sublanes of the value blocks; masks are int32 until consumed.
    """
    gb, a, bb = st_rnd_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bb), 1)

    def row(k):
        base, crnd, alive_of, limit = scalars(k)
        # instance numbers of this ring block; the window's first block is
        # the one holding slot ``base``
        inst = base - base % bb + i * bb + lane                    # (1, BB)

        # Permit: lanes before the window start, and lanes at or past the
        # limit — the window end, or the group's reclaim limit (snapshot
        # watermark + N, DESIGN.md §9) if that comes first — are refused
        # by every acceptor, so their slots survive bit-unchanged.  Past
        # the reclaim limit that is backpressure: the slot's decision has
        # not been drained yet.
        permit = ((inst >= base) & (inst < limit)).astype(jnp.int32)

        # -- the group's acceptor array votes (Phase 2A -> 2B), all at once
        cur_rnd = st_rnd_ref[k]                                    # (A, BB)
        accept = (
            _alive_rows(alive_of, a, bb)
            * permit
            * (crnd >= cur_rnd).astype(jnp.int32)
        )                                                          # (A, BB)
        acc = accept != 0
        mval = values_ref[k]                                       # (V, BB)
        o_rnd_ref[k] = jnp.where(acc, crnd, cur_rnd)
        o_vrnd_ref[k] = jnp.where(acc, crnd, st_vrnd_ref[k])
        for j in range(a):
            o_val_ref[k, j] = jnp.where(
                accept[j:j + 1] != 0, mval, st_val_ref[k, j]
            )

        # -- learner quorum down the acceptor axis.  Every accepting
        # acceptor voted (crnd, mval), so the winning vrnd is crnd where
        # anyone accepted, the agreeing votes are exactly the accepts, and
        # the first agreeing vote's value is the burst value.
        count = jnp.sum(accept, axis=0, keepdims=True)             # (1, BB)
        deliver = count >= quorum
        win = jnp.where(count > 0, crnd, NO_ROUND)
        value = jnp.where(count > 0, mval, 0)                      # (V, BB)

        # -- ring dedup (LearnerState), in place ---------------------------
        ldel = ldel_ref[k]                                         # (1, BB)
        linst = linst_ref[k]
        fresh = (deliver & ~((ldel != 0) & (linst == inst))).astype(jnp.int32)
        o_ldel_ref[k] = ldel | deliver.astype(jnp.int32)
        o_linst_ref[k] = jnp.where(fresh != 0, inst, linst)
        o_lval_ref[k] = jnp.where(fresh != 0, value, lval_ref[k])
        fresh_ref[k] = fresh
        win_ref[k] = win
        value_ref[k] = value

    if gb == 1:
        row(0)
    else:
        jax.lax.fori_loop(0, gb, lambda k, _: row(k), None)


def _cohort_wirepath_kernel(
    # scalar prefetch (SMEM): the index maps and the round body read them
    gsel_ref,       # int32[NB]    selected group-block indices (÷ GB)
    ni_ref,         # int32[G]     per-group window base
    crnd_ref,       # int32[G]     per-group coordinator round
    q_ref,          # int32[1]     quorum (f+1)
    alive_ref,      # int32[G, A]  per-group runtime liveness mask
    lim_ref,        # int32[G]     per-group reclaim limit (first refused inst)
    # inputs (VMEM tiles)
    values_ref,     # int32[GB, V, BB]     burst values (compact rows)
    st_rnd_ref,     # int32[GB, A, BB]     acceptor ring blocks (aliased out)
    st_vrnd_ref,    # int32[GB, A, BB]
    st_val_ref,     # int32[GB, A, V, BB]
    ldel_ref,       # int32[GB, 1, BB]     learner ring blocks (aliased out)
    linst_ref,      # int32[GB, 1, BB]
    lval_ref,       # int32[GB, V, BB]
    # outputs
    o_rnd_ref, o_vrnd_ref, o_val_ref, o_ldel_ref, o_linst_ref, o_lval_ref,
    fresh_ref,      # int32[GB, 1, BB]  (compact rows)
    win_ref,        # int32[GB, 1, BB]
    value_ref,      # int32[GB, V, BB]
):
    gb = st_rnd_ref.shape[0]
    g0 = gsel_ref[pl.program_id(0)] * gb

    def scalars(k):
        g = g0 + k
        return ni_ref[g], crnd_ref[g], lambda j: alive_ref[g, j], lim_ref[g]

    _phase2_rows(
        pl.program_id(1), q_ref[0], scalars,
        values_ref, st_rnd_ref, st_vrnd_ref, st_val_ref,
        ldel_ref, linst_ref, lval_ref,
        o_rnd_ref, o_vrnd_ref, o_val_ref, o_ldel_ref, o_linst_ref, o_lval_ref,
        fresh_ref, win_ref, value_ref,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "group_block", "window_blocks", "interpret"),
)
def cohort_wirepath_round(
    gsel: jax.Array,        # int32[NB]  selected group-block indices (÷ GB)
    next_inst: jax.Array,   # int32[G]  per-group window base
    crnd: jax.Array,        # int32[G]  per-group coordinator round
    quorum: jax.Array,      # int32[]
    alive: jax.Array,       # int32[G, A] (0/1)
    st_rnd: jax.Array,      # int32[G, A, N]   stacked acceptor rings
    st_vrnd: jax.Array,     # int32[G, A, N]
    st_val: jax.Array,      # int32[G, A, N, V]
    ldel: jax.Array,        # int32[G, N]      learner rings
    linst: jax.Array,       # int32[G, N]
    lval: jax.Array,        # int32[G, N, V]
    values: jax.Array,      # int32[NB*GB, B, V]  cohort burst values, compact
    enabled: jax.Array | None = None,  # int32[G] (0/1); None = all enabled
    limit: jax.Array | None = None,    # int32[G]; None = no reclamation
    *,
    block_b: int = DEFAULT_BLOCK_B,
    group_block: int = 1,
    window_blocks: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """One fused Phase-2 round for a *cohort* of groups: the grid visits
    only the ``GB``-aligned group blocks named by ``gsel`` (DESIGN.md §8).

    This is the group-axis analogue of the ring blocking: a dispatch's cost
    scales with the cohort it serves, not with the full capacity ``G``.
    Unselected groups' slabs are never loaded — their rows of the aliased
    state outputs retain their input data, exactly like the unvisited ring
    blocks along the batch axis.  ``values`` and the ``fresh``/``win``/
    ``value`` outputs are *compact*: row ``j*GB + k`` belongs to group
    ``gsel[j]*GB + k``.

    ``group_block > 1`` folds each selected block; the folded *enabled*
    members of a block must share one watermark (the per-cohort lockstep
    condition computed by ``core.plan.cohort_blocks``).  ``window_blocks``
    is the number of ring blocks the grid visits per group block
    (``core.plan.window_blocks``); ``None`` visits enough for a window at
    any offset.
    ``enabled`` marks the cohort: non-members inside a selected block ride
    inert — round forced to NO_ROUND, watermark substituted with the
    block's enabled-lockstep base — and are written back bit-unchanged.

    ``limit`` is the per-group reclamation limit (DESIGN.md §9): the first
    instance the group may NOT sequence into — its snapshot watermark plus
    the ring capacity N.  Lanes at or past the limit are refused by every
    acceptor (state written back unchanged, no delivery), surfacing ring
    exhaustion as backpressure instead of silently overwriting undrained
    slots.  ``None`` grants a full permit (legacy overwrite-on-wrap mode).

    Returns ``(st_rnd', st_vrnd', st_val', ldel', linst', lval',
    fresh[NB*GB, B], win_vrnd[NB*GB, B], value[NB*GB, B, V])`` with the
    state outputs full-width ``(G, ...)`` (aliased in place).
    """
    g, a, n = st_rnd.shape
    c, b, v = values.shape
    bb = ring_block(n, block_b)
    gb = group_block
    nb = gsel.shape[0]
    assert b <= n, "burst may not lap the instance ring"
    assert g % gb == 0, (g, gb)
    assert c == nb * gb, (c, nb, gb)
    nb_ring = n // bb
    nblk = window_blocks or _default_window_blocks(n, bb, b)
    assert nblk <= nb_ring, (nblk, nb_ring)
    grid = (nb, nblk)

    # Ring offset of a selected block comes from its first group's watermark;
    # with group_block == 1 that IS the group's own watermark, with
    # group_block > 1 the caller guarantees the folded enabled members are in
    # lockstep (and disabled members' watermarks are substituted below).
    def ring(gs, i, ni_ref):
        return (ni_ref[gs * gb] // bb + i) % nb_ring

    def stack3(gi, i, gsel_ref, ni_ref, *_):
        gs = gsel_ref[gi]
        return (gs, 0, ring(gs, i, ni_ref))

    def stack4(gi, i, gsel_ref, ni_ref, *_):
        gs = gsel_ref[gi]
        return (gs, 0, 0, ring(gs, i, ni_ref))

    def lanes3(gi, i, *_):
        return (gi, 0, i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((gb, v, bb), lanes3),       # values (compact)
            pl.BlockSpec((gb, a, bb), stack3),       # st_rnd
            pl.BlockSpec((gb, a, bb), stack3),       # st_vrnd
            pl.BlockSpec((gb, a, v, bb), stack4),    # st_val
            pl.BlockSpec((gb, 1, bb), stack3),       # ldel
            pl.BlockSpec((gb, 1, bb), stack3),       # linst
            pl.BlockSpec((gb, v, bb), stack3),       # lval
        ],
        out_specs=[
            pl.BlockSpec((gb, a, bb), stack3),       # st_rnd'
            pl.BlockSpec((gb, a, bb), stack3),       # st_vrnd'
            pl.BlockSpec((gb, a, v, bb), stack4),    # st_val'
            pl.BlockSpec((gb, 1, bb), stack3),       # ldel'
            pl.BlockSpec((gb, 1, bb), stack3),       # linst'
            pl.BlockSpec((gb, v, bb), stack3),       # lval'
            pl.BlockSpec((gb, 1, bb), lanes3),       # fresh (compact)
            pl.BlockSpec((gb, 1, bb), lanes3),       # win_vrnd (compact)
            pl.BlockSpec((gb, v, bb), lanes3),       # value (compact)
        ],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((g, a, n), jnp.int32),
        jax.ShapeDtypeStruct((g, a, n), jnp.int32),
        jax.ShapeDtypeStruct((g, a, v, n), jnp.int32),
        jax.ShapeDtypeStruct((g, 1, n), jnp.int32),
        jax.ShapeDtypeStruct((g, 1, n), jnp.int32),
        jax.ShapeDtypeStruct((g, v, n), jnp.int32),
        jax.ShapeDtypeStruct((c, 1, nblk * bb), jnp.int32),
        jax.ShapeDtypeStruct((c, 1, nblk * bb), jnp.int32),
        jax.ShapeDtypeStruct((c, v, nblk * bb), jnp.int32),
    ]
    fn = pl.pallas_call(
        _cohort_wirepath_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        # all five state arrays update in place: inputs 7..12 (after the 6
        # scalar-prefetch args) alias outputs 0..5 — device-resident state
        input_output_aliases={7: 0, 8: 1, 9: 2, 10: 3, 11: 4, 12: 5},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )
    ni = jnp.asarray(next_inst, jnp.int32).reshape((g,))
    cr = jnp.asarray(crnd, jnp.int32).reshape((g,))
    if enabled is not None:
        en = jnp.asarray(enabled, jnp.int32).reshape((g,)) != 0
        # a disabled group decides (and mutates) nothing: NO_ROUND rejects
        cr = jnp.where(en, cr, jnp.int32(NO_ROUND))
        if gb > 1:
            # a folded block has ONE ring offset (its first group's
            # watermark); substitute disabled members with the block's
            # enabled-lockstep base so their stray watermarks cannot skew
            # it — their windows are written back unchanged wherever they
            # land, so the substitution is state-exact
            enb = en.reshape(g // gb, gb)
            nib = ni.reshape(g // gb, gb)
            base = jnp.max(
                jnp.where(enb, nib, jnp.iinfo(jnp.int32).min), axis=1
            )
            base = jnp.where(jnp.any(enb, axis=1), base, 0)
            ni = jnp.where(enb, nib, base[:, None]).reshape((g,))
    q = jnp.asarray(quorum, jnp.int32).reshape((1,))
    al = jnp.asarray(alive, jnp.int32).reshape((g, a))
    gs = jnp.asarray(gsel, jnp.int32).reshape((nb,))
    # the window ends the permit: no lane at or past base + B votes
    lim = ni + b
    if limit is not None:
        lim = jnp.minimum(lim, jnp.asarray(limit, jnp.int32).reshape((g,)))
    rows = (gs[:, None] * gb + jnp.arange(gb, dtype=jnp.int32)).reshape(c)
    off = ni[rows] % bb
    return _window_outputs(
        fn(gs, ni, cr, q, al, lim, _to_ring_lanes(values, off, nblk * bb),
           st_rnd, st_vrnd, _v_major(st_val), ldel[:, None], linst[:, None],
           _v_major(lval)),
        off, b,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "group_block", "window_blocks", "interpret"),
)
def multigroup_wirepath_round(
    next_inst: jax.Array,   # int32[G]  per-group window base
    crnd: jax.Array,        # int32[G]  per-group coordinator round
    quorum: jax.Array,      # int32[]
    alive: jax.Array,       # int32[G, A] (0/1)
    st_rnd: jax.Array,      # int32[G, A, N]   stacked acceptor rings
    st_vrnd: jax.Array,     # int32[G, A, N]
    st_val: jax.Array,      # int32[G, A, N, V]
    ldel: jax.Array,        # int32[G, N]      learner rings
    linst: jax.Array,       # int32[G, N]
    lval: jax.Array,        # int32[G, N, V]
    values: jax.Array,      # int32[G, B, V]   per-group burst values
    enabled: jax.Array | None = None,  # int32[G] (0/1); None = all enabled
    limit: jax.Array | None = None,    # int32[G]; None = no reclamation
    *,
    block_b: int = DEFAULT_BLOCK_B,
    group_block: int = 1,
    window_blocks: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """One fused Phase-2 round for G device-resident groups; single dispatch.

    The full-width slice of ``cohort_wirepath_round``: every group block is
    selected, so the compact value/output layout coincides with the
    ``(G, ...)`` layout.  ``group_block > 1`` folds that many groups into
    each grid step (see the module docstring); the folded *enabled* groups
    of a block must share one watermark — the caller's
    responsibility (``core.plan.fold_width_full`` picks the widest legal
    fold from the host watermark mirrors).  ``enabled`` is the vacant/
    frozen mask: disabled groups get their round forced to NO_ROUND and,
    when folding, their watermark substituted with the block's
    enabled-lockstep base — they ride the dispatch inert and bit-unchanged.

    Returns ``(st_rnd', st_vrnd', st_val', ldel', linst', lval',
    fresh[G, B], win_vrnd[G, B], value[G, B, V])``.
    """
    g = st_rnd.shape[0]
    assert g % group_block == 0, (g, group_block)
    gsel = jnp.arange(g // group_block, dtype=jnp.int32)
    return cohort_wirepath_round(
        gsel, next_inst, crnd, quorum, alive,
        st_rnd, st_vrnd, st_val, ldel, linst, lval, values, enabled, limit,
        block_b=block_b, group_block=group_block,
        window_blocks=window_blocks, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Persistent K-round entry: a whole wave of Phase-2 rounds per pallas_call
# ---------------------------------------------------------------------------
def _persistent_wirepath_kernel(
    # scalar prefetch (SMEM): the index maps and the round body read them
    gsel_ref,       # int32[NB]    selected group-block indices (÷ GB)
    wni_ref,        # int32[K, G]  wave descriptor: per-round window bases
    crnd_ref,       # int32[G]     per-group coordinator round
    q_ref,          # int32[1]     quorum (f+1)
    alive_ref,      # int32[G, A]  per-group runtime liveness mask
    lim_ref,        # int32[G]     per-group reclaim limit
    wen_ref,        # int32[K, G]  wave descriptor: per-round enables
    # inputs (VMEM tiles)
    values_ref,     # int32[GB, V, BB]     round k's burst values
    st_rnd_ref,     # int32[GB, A, BB]     acceptor ring blocks (aliased out)
    st_vrnd_ref,    # int32[GB, A, BB]
    st_val_ref,     # int32[GB, A, V, BB]
    ldel_ref,       # int32[GB, 1, BB]     learner ring blocks (aliased out)
    linst_ref,      # int32[GB, 1, BB]
    lval_ref,       # int32[GB, V, BB]
    # outputs
    o_rnd_ref, o_vrnd_ref, o_val_ref, o_ldel_ref, o_linst_ref, o_lval_ref,
    fresh_ref,      # int32[GB, 1, BB]  round k's rows
    win_ref,        # int32[GB, 1, BB]
    value_ref,      # int32[GB, V, BB]
):
    kk = pl.program_id(0)
    gb = st_rnd_ref.shape[0]
    g0 = gsel_ref[pl.program_id(1)] * gb

    def scalars(k):
        g = g0 + k
        # a group sitting out round kk (wen == 0) rides the round inert:
        # round presented as NO_ROUND so its acceptors reject every slot,
        # its window (unchanged from its last enabled round) written back
        # bit-identical
        crnd = jnp.where(wen_ref[kk, g] != 0, crnd_ref[g], NO_ROUND)
        return wni_ref[kk, g], crnd, lambda j: alive_ref[g, j], lim_ref[g]

    _phase2_rows(
        pl.program_id(2), q_ref[0], scalars,
        values_ref, st_rnd_ref, st_vrnd_ref, st_val_ref,
        ldel_ref, linst_ref, lval_ref,
        o_rnd_ref, o_vrnd_ref, o_val_ref, o_ldel_ref, o_linst_ref, o_lval_ref,
        fresh_ref, win_ref, value_ref,
    )


@functools.partial(
    jax.jit, static_argnames=("block_b", "group_block", "interpret")
)
def persistent_wirepath_round(
    gsel: jax.Array,        # int32[NB]    selected group-block indices (÷ GB)
    wni: jax.Array,         # int32[K, G]  per-round window bases (BB-aligned)
    wen: jax.Array,         # int32[K, G]  per-round participation (0/1)
    crnd: jax.Array,        # int32[G]     per-group coordinator round
    quorum: jax.Array,      # int32[]
    alive: jax.Array,       # int32[G, A] (0/1)
    st_rnd: jax.Array,      # int32[G, A, N]   stacked acceptor rings
    st_vrnd: jax.Array,     # int32[G, A, N]
    st_val: jax.Array,      # int32[G, A, N, V]
    ldel: jax.Array,        # int32[G, N]      learner rings
    linst: jax.Array,       # int32[G, N]
    lval: jax.Array,        # int32[G, N, V]
    values: jax.Array,      # int32[K, NB*GB, B, V]  wave values, compact rows
    limit: jax.Array | None = None,    # int32[G]; None = no reclamation
    *,
    block_b: int = DEFAULT_BLOCK_B,
    group_block: int = 1,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """K Phase-2 rounds in ONE ``pallas_call``: the persistent wire path.

    The single-round dispatch pays a host round-trip per round, and on small
    bursts that dispatch overhead — not consensus arithmetic — is the
    throughput ceiling (the paper's host-boundary argument; BENCH_wirepath
    rows ``trickle_*``).  Here the whole chunk *wave* is device-resident:
    the grid grows a leading sequential round axis ``K``, each round k
    re-runs sequence -> vote -> quorum -> learner dedup over its own ring
    window, and host sync (watermarks, the ``fresh``/``value`` read-back)
    happens once per K rounds instead of once per round.

    The **wave descriptor** generalizes the cohort scalar-prefetch vectors
    to a per-round table:

      * ``wni[k, g]`` — group ``g``'s window base at round ``k``, a multiple
        of the ring block ``ring_block(N, block_b)``, which must divide B.  The host
        precomputes the cumulative walk ``wni[k+1] = wni[k] + B·wen[k]``
        (and applies the folded-block base substitution per round), so the
        index maps stay pure lookups: block ``gi`` of round ``k`` maps its
        rings at ``(wni[k, gsel[gi]·GB] // BB + i) % (N // BB)``.
      * ``wen[k, g]`` — whether ``g`` participates in round ``k`` (the
        per-round burst length, quantized: a group either rides a full
        ``B``-slot window or sits the round out).  A non-participant is
        presented at NO_ROUND with its window frozen, so it is written back
        bit-unchanged — mid-wave freezes land exactly between rounds.
      * ``gsel`` — the cohort group-block selection, shared by all K rounds
        (one wave = one cohort).

    Rounds are *sequential by construction*: round k+1's windows are
    disjoint from round k's (enabled windows advance by B; ``K·B <= N``
    keeps a wave from lapping the ring), and revisited blocks belong only
    to non-participants whose writeback is bit-identical, so grid-step
    pipelining can never read a stale block that matters.

    Returns ``(st_rnd', st_vrnd', st_val', ldel', linst', lval',
    fresh[K, NB*GB, B], win_vrnd[K, NB*GB, B], value[K, NB*GB, B, V])`` —
    per-round compact outputs, state aliased in place.
    """
    g, a, n = st_rnd.shape
    k, c, b, v = values.shape
    bb = ring_block(n, block_b)
    gb = group_block
    nb = gsel.shape[0]
    # block-aligned windows: consecutive rounds never share a ring block
    # (a shared block would be read before the previous round's write lands)
    assert b % bb == 0, (b, bb)
    assert k * b <= n, "persistent wave may not lap the instance ring"
    assert g % gb == 0, (g, gb)
    assert c == nb * gb, (c, nb, gb)
    assert wni.shape == (k, g), (wni.shape, k, g)
    assert wen.shape == (k, g), (wen.shape, k, g)
    nb_ring = n // bb
    grid = (k, nb, b // bb)

    def ring(kk, gs, i, wni_ref):
        return (wni_ref[kk, gs * gb] // bb + i) % nb_ring

    def stack3(kk, gi, i, gsel_ref, wni_ref, *_):
        gs = gsel_ref[gi]
        return (gs, 0, ring(kk, gs, i, wni_ref))

    def stack4(kk, gi, i, gsel_ref, wni_ref, *_):
        gs = gsel_ref[gi]
        return (gs, 0, 0, ring(kk, gs, i, wni_ref))

    # per-round rows live at kk*NB*GB + compact row: block kk*NB + gi
    def lanes3(kk, gi, i, *_):
        return (kk * nb + gi, 0, i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=grid,
        in_specs=[
            pl.BlockSpec((gb, v, bb), lanes3),       # values (compact, per-k)
            pl.BlockSpec((gb, a, bb), stack3),       # st_rnd
            pl.BlockSpec((gb, a, bb), stack3),       # st_vrnd
            pl.BlockSpec((gb, a, v, bb), stack4),    # st_val
            pl.BlockSpec((gb, 1, bb), stack3),       # ldel
            pl.BlockSpec((gb, 1, bb), stack3),       # linst
            pl.BlockSpec((gb, v, bb), stack3),       # lval
        ],
        out_specs=[
            pl.BlockSpec((gb, a, bb), stack3),       # st_rnd'
            pl.BlockSpec((gb, a, bb), stack3),       # st_vrnd'
            pl.BlockSpec((gb, a, v, bb), stack4),    # st_val'
            pl.BlockSpec((gb, 1, bb), stack3),       # ldel'
            pl.BlockSpec((gb, 1, bb), stack3),       # linst'
            pl.BlockSpec((gb, v, bb), stack3),       # lval'
            pl.BlockSpec((gb, 1, bb), lanes3),       # fresh (compact, per-k)
            pl.BlockSpec((gb, 1, bb), lanes3),       # win_vrnd
            pl.BlockSpec((gb, v, bb), lanes3),       # value
        ],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((g, a, n), jnp.int32),
        jax.ShapeDtypeStruct((g, a, n), jnp.int32),
        jax.ShapeDtypeStruct((g, a, v, n), jnp.int32),
        jax.ShapeDtypeStruct((g, 1, n), jnp.int32),
        jax.ShapeDtypeStruct((g, 1, n), jnp.int32),
        jax.ShapeDtypeStruct((g, v, n), jnp.int32),
        jax.ShapeDtypeStruct((k * c, 1, b), jnp.int32),
        jax.ShapeDtypeStruct((k * c, 1, b), jnp.int32),
        jax.ShapeDtypeStruct((k * c, v, b), jnp.int32),
    ]
    fn = pl.pallas_call(
        _persistent_wirepath_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        # state arrays update in place: inputs 8..13 (after the 7 scalar-
        # prefetch args) alias outputs 0..5 — device-resident across rounds
        input_output_aliases={8: 0, 9: 1, 10: 2, 11: 3, 12: 4, 13: 5},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )
    cr = jnp.asarray(crnd, jnp.int32).reshape((g,))
    wenk = jnp.asarray(wen, jnp.int32).reshape((k, g)) != 0
    wnik = jnp.asarray(wni, jnp.int32).reshape((k, g))
    if gb > 1:
        # per round, a folded block has ONE ring offset (its first group's
        # window base); substitute that round's non-participants with the
        # block's participating-lockstep base, exactly as the single-round
        # cohort entry does — state-exact because non-participants are
        # written back unchanged wherever their window lands
        enb = wenk.reshape(k, g // gb, gb)
        nib = wnik.reshape(k, g // gb, gb)
        base = jnp.max(
            jnp.where(enb, nib, jnp.iinfo(jnp.int32).min), axis=2
        )
        base = jnp.where(jnp.any(enb, axis=2), base, 0)
        wnik = jnp.where(enb, nib, base[..., None]).reshape((k, g))
    q = jnp.asarray(quorum, jnp.int32).reshape((1,))
    al = jnp.asarray(alive, jnp.int32).reshape((g, a))
    gs = jnp.asarray(gsel, jnp.int32).reshape((nb,))
    wenk = wenk.astype(jnp.int32)
    if limit is None:
        lim = jnp.full((g,), jnp.iinfo(jnp.int32).max, jnp.int32)
    else:
        lim = jnp.asarray(limit, jnp.int32).reshape((g,))
    outs = fn(
        gs, wnik, cr, q, al, lim, wenk,
        _v_major(values).reshape(k * c, v, b),
        st_rnd, st_vrnd, _v_major(st_val), ldel[:, None], linst[:, None],
        _v_major(lval),
    )
    return _host_state(outs) + (
        outs[6].reshape(k, c, b),
        outs[7].reshape(k, c, b),
        _v_major(outs[8].reshape(k, c, v, b)),
    )


def shard_slab_round(
    group_offset: jax.Array,  # int32[]  first global group id of this slab
    next_inst: jax.Array,     # int32[G_global]  replicated watermark vector
    crnd: jax.Array,          # int32[G_global]  replicated round vector
    quorum: jax.Array,        # int32[]
    alive: jax.Array,         # int32[G_global, A]  replicated liveness
    st_rnd: jax.Array,        # int32[Gl, A, N]   this shard's acceptor slab
    st_vrnd: jax.Array,       # int32[Gl, A, N]
    st_val: jax.Array,        # int32[Gl, A, N, V]
    ldel: jax.Array,          # int32[Gl, N]      this shard's learner slab
    linst: jax.Array,         # int32[Gl, N]
    lval: jax.Array,          # int32[Gl, N, V]
    values: jax.Array,        # int32[Gl, B, V]   this shard's burst slab
    enabled: jax.Array | None = None,  # int32[G_global] (0/1) replicated
    limit: jax.Array | None = None,    # int32[G_global] replicated
    *,
    block_b: int = DEFAULT_BLOCK_B,
    group_block: int = 1,
    window_blocks: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """Local-slab entry point for the groups-sharded dataplane (DESIGN.md §6).

    Runs ``multigroup_wirepath_round`` on ONE shard's contiguous slab of
    ``Gl = G_global / n_shards`` groups.  The per-group scalar vectors
    (watermarks, rounds, liveness, and the membership ``enabled`` mask) stay
    *global and replicated* — they are tiny, host-mutated metadata — and
    ``group_offset`` selects this shard's window so per-group scalars index
    correctly inside the shard.  Designed to be called inside ``shard_map``
    with the slab arrays partitioned over a ``groups`` mesh axis
    (``core.fabric.make_sharded_multigroup_round``).
    """
    gl, a = st_rnd.shape[0], st_rnd.shape[1]
    off = jnp.asarray(group_offset, jnp.int32).reshape(())
    ni = jax.lax.dynamic_slice(
        jnp.asarray(next_inst, jnp.int32).reshape((-1,)), (off,), (gl,)
    )
    cr = jax.lax.dynamic_slice(
        jnp.asarray(crnd, jnp.int32).reshape((-1,)), (off,), (gl,)
    )
    al = jax.lax.dynamic_slice(
        jnp.asarray(alive, jnp.int32).reshape((-1, a)),
        (off, jnp.int32(0)),
        (gl, a),
    )
    en = None
    if enabled is not None:
        en = jax.lax.dynamic_slice(
            jnp.asarray(enabled, jnp.int32).reshape((-1,)), (off,), (gl,)
        )
    lim = None
    if limit is not None:
        lim = jax.lax.dynamic_slice(
            jnp.asarray(limit, jnp.int32).reshape((-1,)), (off,), (gl,)
        )
    return multigroup_wirepath_round(
        ni, cr, quorum, al,
        st_rnd, st_vrnd, st_val, ldel, linst, lval, values, en, lim,
        block_b=block_b, group_block=group_block,
        window_blocks=window_blocks, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Packed ragged-shard entry: C resident lanes, slab rows routed by segment id
# ---------------------------------------------------------------------------
def _packed_shard_kernel(
    # scalar prefetch (SMEM), all per LANE: the index maps route each lane
    # to its slab row, the round body reads the lane's scalars
    ni_ref,         # int32[C]     per-lane window base
    crnd_ref,       # int32[C]     per-lane coordinator round
    q_ref,          # int32[1]     quorum (f+1)
    alive_ref,      # int32[C, A]  per-lane liveness row
    lim_ref,        # int32[C]     per-lane reclaim limit
    seg_ref,        # int32[C]     per-lane slab row (index maps only)
    # inputs (VMEM tiles)
    values_ref,     # int32[1, V, BB]      the lane's burst values
    st_rnd_ref,     # int32[1, A, BB]      acceptor ring blocks (aliased out)
    st_vrnd_ref,    # int32[1, A, BB]
    st_val_ref,     # int32[1, A, V, BB]
    ldel_ref,       # int32[1, 1, BB]      learner ring blocks (aliased out)
    linst_ref,      # int32[1, 1, BB]
    lval_ref,       # int32[1, V, BB]
    # outputs
    o_rnd_ref, o_vrnd_ref, o_val_ref, o_ldel_ref, o_linst_ref, o_lval_ref,
    fresh_ref,      # int32[1, 1, BB]  (packed lanes)
    win_ref,        # int32[1, 1, BB]
    value_ref,      # int32[1, V, BB]
):
    del seg_ref
    lane = pl.program_id(0)

    def scalars(_k):
        return (
            ni_ref[lane], crnd_ref[lane],
            lambda j: alive_ref[lane, j], lim_ref[lane],
        )

    _phase2_rows(
        pl.program_id(1), q_ref[0], scalars,
        values_ref, st_rnd_ref, st_vrnd_ref, st_val_ref,
        ldel_ref, linst_ref, lval_ref,
        o_rnd_ref, o_vrnd_ref, o_val_ref, o_ldel_ref, o_linst_ref, o_lval_ref,
        fresh_ref, win_ref, value_ref,
    )


@functools.partial(
    jax.jit, static_argnames=("block_b", "window_blocks", "interpret")
)
def packed_shard_round(
    segids: jax.Array,      # int32[C]  per-lane local slab row (0..Gl)
    next_inst: jax.Array,   # int32[C]  per-lane window base
    crnd: jax.Array,        # int32[C]  per-lane coordinator round
    quorum: jax.Array,      # int32[]
    alive: jax.Array,       # int32[C, A] (0/1)
    st_rnd: jax.Array,      # int32[Gl, A, N]   this shard's acceptor slab
    st_vrnd: jax.Array,     # int32[Gl, A, N]
    st_val: jax.Array,      # int32[Gl, A, N, V]
    ldel: jax.Array,        # int32[Gl, N]      this shard's learner slab
    linst: jax.Array,       # int32[Gl, N]
    lval: jax.Array,        # int32[Gl, N, V]
    values: jax.Array,      # int32[C, B, V]  packed burst values, lane order
    enabled: jax.Array | None = None,  # int32[C] (0/1); None = all lanes real
    limit: jax.Array | None = None,    # int32[C]; None = no reclamation
    *,
    block_b: int = DEFAULT_BLOCK_B,
    window_blocks: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """One fused Phase-2 round over a shard's *packed* lane table: the grid
    visits ``C`` uniform lanes and a ``segids`` scalar-prefetch vector routes
    each lane to the slab row it serves — the GShard MoE input-packing idiom
    (ragged segments inside a fixed dispatch shape) applied to the group
    slabs (DESIGN.md §13).

    Where ``shard_slab_round`` always walks the shard's full ``Gl``-row slab
    (cold cohorts pay full-width slab cost), here the dispatch costs what
    its *resident, enabled* lanes cost: lane ``j`` processes slab row
    ``segids[j]`` with its own watermark/round/liveness/limit scalars — all
    per-LANE vectors, packed by the caller in lane order.  Slab rows not
    named by any lane are never loaded; their rows of the aliased state
    outputs retain the input data, exactly like unselected cohort blocks.

    Pad lanes (``enabled == 0``) make the lane count uniform across shards
    (shard_map shape uniformity).  A pad rides inert — round forced to
    NO_ROUND, so its row is loaded and stored back bit-identical — and its
    segment id is *redirected to a provably-unused slab row*: enabled lanes
    must name pairwise-distinct rows, so when any pad exists the enabled
    count is < C <= Gl and a free row exists.  That redirection is the
    safety argument under grid-step pipelining (the same argument as the
    persistent kernel's revisited blocks): every slab row is touched either
    by its single enabled lane, or only by pads whose writeback is
    bit-identical — no interleaving can publish a stale block.

    Returns ``(st_rnd', st_vrnd', st_val', ldel', linst', lval',
    fresh[C, B], win_vrnd[C, B], value[C, B, V])`` with the state outputs
    full-slab ``(Gl, ...)`` (aliased in place).
    """
    gl, a, n = st_rnd.shape
    c, b, v = values.shape
    bb = ring_block(n, block_b)
    assert b <= n, "burst may not lap the instance ring"
    assert c <= gl, (
        "packed lane count may not exceed the slab height (pad redirection "
        "needs a free row whenever pads exist)", c, gl,
    )
    nb_ring = n // bb
    nblk = window_blocks or _default_window_blocks(n, bb, b)
    assert nblk <= nb_ring, (nblk, nb_ring)
    grid = (c, nblk)

    # Each lane's ring offset comes from its OWN watermark; its slab row
    # from its segment id — both per-lane prefetch lookups.
    def ring(gi, i, ni_ref):
        return (ni_ref[gi] // bb + i) % nb_ring

    def stack3(gi, i, ni_ref, cr_ref, q_ref, al_ref, lim_ref, seg_ref):
        return (seg_ref[gi], 0, ring(gi, i, ni_ref))

    def stack4(gi, i, ni_ref, cr_ref, q_ref, al_ref, lim_ref, seg_ref):
        return (seg_ref[gi], 0, 0, ring(gi, i, ni_ref))

    def lanes3(gi, i, *_):
        return (gi, 0, i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, v, bb), lanes3),        # values (packed)
            pl.BlockSpec((1, a, bb), stack3),        # st_rnd
            pl.BlockSpec((1, a, bb), stack3),        # st_vrnd
            pl.BlockSpec((1, a, v, bb), stack4),     # st_val
            pl.BlockSpec((1, 1, bb), stack3),        # ldel
            pl.BlockSpec((1, 1, bb), stack3),        # linst
            pl.BlockSpec((1, v, bb), stack3),        # lval
        ],
        out_specs=[
            pl.BlockSpec((1, a, bb), stack3),        # st_rnd'
            pl.BlockSpec((1, a, bb), stack3),        # st_vrnd'
            pl.BlockSpec((1, a, v, bb), stack4),     # st_val'
            pl.BlockSpec((1, 1, bb), stack3),        # ldel'
            pl.BlockSpec((1, 1, bb), stack3),        # linst'
            pl.BlockSpec((1, v, bb), stack3),        # lval'
            pl.BlockSpec((1, 1, bb), lanes3),        # fresh (packed)
            pl.BlockSpec((1, 1, bb), lanes3),        # win_vrnd (packed)
            pl.BlockSpec((1, v, bb), lanes3),        # value (packed)
        ],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((gl, a, n), jnp.int32),
        jax.ShapeDtypeStruct((gl, a, n), jnp.int32),
        jax.ShapeDtypeStruct((gl, a, v, n), jnp.int32),
        jax.ShapeDtypeStruct((gl, 1, n), jnp.int32),
        jax.ShapeDtypeStruct((gl, 1, n), jnp.int32),
        jax.ShapeDtypeStruct((gl, v, n), jnp.int32),
        jax.ShapeDtypeStruct((c, 1, nblk * bb), jnp.int32),
        jax.ShapeDtypeStruct((c, 1, nblk * bb), jnp.int32),
        jax.ShapeDtypeStruct((c, v, nblk * bb), jnp.int32),
    ]
    fn = pl.pallas_call(
        _packed_shard_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        # all five state slabs update in place: inputs 7..12 (after the 6
        # scalar-prefetch args) alias outputs 0..5 — device-resident state
        input_output_aliases={7: 0, 8: 1, 9: 2, 10: 3, 11: 4, 12: 5},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )
    ni = jnp.asarray(next_inst, jnp.int32).reshape((c,))
    cr = jnp.asarray(crnd, jnp.int32).reshape((c,))
    seg = jnp.asarray(segids, jnp.int32).reshape((c,))
    if enabled is not None:
        en = jnp.asarray(enabled, jnp.int32).reshape((c,)) != 0
        # a pad lane decides (and mutates) nothing: NO_ROUND rejects
        cr = jnp.where(en, cr, jnp.int32(NO_ROUND))
        # pad redirection: scatter enabled rows into a (Gl,) usage map (pads
        # dropped past the end), then point every pad at the first unused
        # row with an aligned window base — see the safety argument above
        used = (
            jnp.zeros((gl,), jnp.int32)
            .at[jnp.where(en, seg, gl)]
            .set(1, mode="drop")
        )
        pad_row = jnp.argmin(used).astype(jnp.int32)
        seg = jnp.where(en, seg, pad_row)
        ni = jnp.where(en, ni, 0)
    q = jnp.asarray(quorum, jnp.int32).reshape((1,))
    al = jnp.asarray(alive, jnp.int32).reshape((c, a))
    # the window ends the permit: no lane at or past base + B votes
    lim = ni + b
    if limit is not None:
        lim = jnp.minimum(lim, jnp.asarray(limit, jnp.int32).reshape((c,)))
    off = ni % bb
    return _window_outputs(
        fn(ni, cr, q, al, lim, seg, _to_ring_lanes(values, off, nblk * bb),
           st_rnd, st_vrnd, _v_major(st_val), ldel[:, None], linst[:, None],
           _v_major(lval)),
        off, b,
    )


@functools.partial(
    jax.jit, static_argnames=("block_b", "window_blocks", "interpret")
)
def wirepath_round(
    next_inst: jax.Array,   # int32[]  absolute window base
    crnd: jax.Array,        # int32[]
    quorum: jax.Array,      # int32[]
    alive: jax.Array,       # int32[A] (0/1)
    st_rnd: jax.Array,      # int32[A, N]   stacked acceptor rings
    st_vrnd: jax.Array,     # int32[A, N]
    st_val: jax.Array,      # int32[A, N, V]
    ldel: jax.Array,        # int32[N]      learner ring
    linst: jax.Array,       # int32[N]
    lval: jax.Array,        # int32[N, V]
    values: jax.Array,      # int32[B, V]   burst values
    limit: jax.Array | None = None,  # int32[]; None = no reclamation
    *,
    block_b: int = DEFAULT_BLOCK_B,
    window_blocks: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """One fused Phase-2 round for a single group: the G=1 slice of
    ``multigroup_wirepath_round`` (same kernel, one group on the grid).

    Returns ``(st_rnd', st_vrnd', st_val', ldel', linst', lval',
    fresh[B], win_vrnd[B], value[B, V])``.
    """
    outs = multigroup_wirepath_round(
        jnp.asarray(next_inst, jnp.int32).reshape((1,)),
        jnp.asarray(crnd, jnp.int32).reshape((1,)),
        quorum,
        jnp.asarray(alive, jnp.int32)[None],
        st_rnd[None],
        st_vrnd[None],
        st_val[None],
        ldel[None],
        linst[None],
        lval[None],
        values[None],
        None,
        None if limit is None else jnp.asarray(limit, jnp.int32).reshape((1,)),
        block_b=block_b,
        window_blocks=window_blocks,
        interpret=interpret,
    )
    return tuple(x[0] for x in outs)


# ---------------------------------------------------------------------------
# Staged variant: all-acceptor vote with per-acceptor vote output
# ---------------------------------------------------------------------------
def _vote_all_kernel(
    base_ref,       # int32[1]  window base slot (BB-aligned)
    alive_ref,      # int32[A]
    msgtype_ref,    # int32[1, BB]
    msg_rnd_ref,    # int32[1, BB]
    msg_val_ref,    # int32[V, BB]
    st_rnd_ref,     # int32[A, BB]  (aliased out)
    st_vrnd_ref,    # int32[A, BB]
    st_val_ref,     # int32[A, V, BB]
    o_rnd_ref,      # int32[A, BB]
    o_vrnd_ref,     # int32[A, BB]
    o_val_ref,      # int32[A, V, BB]
    vt_ref,         # int32[A, BB]  vote msgtype
    vr_ref,         # int32[A, BB]  vote rnd
    vv_ref,         # int32[A, BB]  vote vrnd
    vs_ref,         # int32[A, BB]  vote swid
    vval_ref,       # int32[A, V, BB]
):
    a, bb = st_rnd_ref.shape
    msgtype = msgtype_ref[...]                                   # (1, BB)
    mrnd = msg_rnd_ref[...]                                      # (1, BB)
    mval = msg_val_ref[...]
    cur_rnd = st_rnd_ref[...]
    cur_vrnd = st_vrnd_ref[...]

    is_p2 = (msgtype == MSG_P2A) | (msgtype == MSG_NOP)          # (1, BB)
    accept = (
        _alive_rows(lambda j: alive_ref[j], a, bb)
        * is_p2.astype(jnp.int32)
        * (mrnd >= cur_rnd).astype(jnp.int32)
    )                                                            # (A, BB)
    acc = accept != 0

    o_rnd_ref[...] = jnp.where(acc, mrnd, cur_rnd)
    o_vrnd_ref[...] = jnp.where(acc, mrnd, cur_vrnd)
    for j in range(a):
        acc_j = accept[j:j + 1] != 0                             # (1, BB)
        o_val_ref[j] = jnp.where(acc_j, mval, st_val_ref[j])
        vval_ref[j] = jnp.where(acc_j, mval, 0)

    vt_ref[...] = jnp.where(acc, MSG_P2B, MSG_REJECT).astype(jnp.int32)
    vr_ref[...] = jnp.where(acc, mrnd, cur_rnd)
    vv_ref[...] = jnp.where(acc, mrnd, cur_vrnd)
    vs_ref[...] = jax.lax.broadcasted_iota(jnp.int32, (a, bb), 0)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def acceptor_vote_all_window(
    st_rnd: jax.Array,      # int32[A, N]
    st_vrnd: jax.Array,     # int32[A, N]
    st_val: jax.Array,      # int32[A, N, V]
    base: jax.Array,        # int32[]  window base, BB-aligned
    alive: jax.Array,       # int32[A]
    msgtype: jax.Array,     # int32[B]
    msg_rnd: jax.Array,     # int32[B]
    msg_val: jax.Array,     # int32[B, V]
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """Whole-array Phase-2 vote on a contiguous window, one dispatch.

    The staged sibling of ``wirepath_round`` for when votes must surface as
    messages (per-learner fan-out over SimNet).  The window must be
    block-aligned: ``ring_block(N, block_b)`` divides ``base`` and B.  Returns
    ``(st_rnd', st_vrnd', st_val', vote_type[A,B], vote_rnd[A,B],
    vote_vrnd[A,B], vote_swid[A,B], vote_val[A,B,V])``.
    """
    a, n = st_rnd.shape
    b, v = msg_val.shape
    bb = ring_block(n, block_b)
    assert b % bb == 0, (b, bb)
    assert b <= n, "burst may not lap the instance ring"
    nb_ring = n // bb
    grid = (b // bb,)

    def stack2(i, base_ref, *_):
        return (0, (base_ref[0] // bb + i) % nb_ring)

    def stack3(i, base_ref, *_):
        return (0, 0, (base_ref[0] // bb + i) % nb_ring)

    def vote2(i, *_):
        return (0, i)

    def vote3(i, *_):
        return (0, 0, i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bb), vote2),        # msgtype
            pl.BlockSpec((1, bb), vote2),        # msg_rnd
            pl.BlockSpec((v, bb), vote2),        # msg_val
            pl.BlockSpec((a, bb), stack2),       # st_rnd
            pl.BlockSpec((a, bb), stack2),       # st_vrnd
            pl.BlockSpec((a, v, bb), stack3),    # st_val
        ],
        out_specs=[
            pl.BlockSpec((a, bb), stack2),       # st_rnd'
            pl.BlockSpec((a, bb), stack2),       # st_vrnd'
            pl.BlockSpec((a, v, bb), stack3),    # st_val'
            pl.BlockSpec((a, bb), vote2),        # vote_type
            pl.BlockSpec((a, bb), vote2),        # vote_rnd
            pl.BlockSpec((a, bb), vote2),        # vote_vrnd
            pl.BlockSpec((a, bb), vote2),        # vote_swid
            pl.BlockSpec((a, v, bb), vote3),     # vote_val
        ],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((a, n), jnp.int32),
        jax.ShapeDtypeStruct((a, n), jnp.int32),
        jax.ShapeDtypeStruct((a, v, n), jnp.int32),
        jax.ShapeDtypeStruct((a, b), jnp.int32),
        jax.ShapeDtypeStruct((a, b), jnp.int32),
        jax.ShapeDtypeStruct((a, b), jnp.int32),
        jax.ShapeDtypeStruct((a, b), jnp.int32),
        jax.ShapeDtypeStruct((a, v, b), jnp.int32),
    ]
    fn = pl.pallas_call(
        _vote_all_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        # stacked rings in place: inputs 5,6,7 alias outputs 0,1,2
        input_output_aliases={5: 0, 6: 1, 7: 2},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )
    base = jnp.asarray(base, jnp.int32).reshape((1,))
    al = jnp.asarray(alive, jnp.int32).reshape((a,))
    # (B,) header vectors ride as (1, B) — a one-row block of a 2-D array
    # is legal at any B, a 1-D block is not (XLA tiles s32[B] by 1024) —
    # and value words V-major, as in the fused kernels
    outs = fn(
        base, al, msgtype.reshape(1, b), msg_rnd.reshape(1, b),
        _v_major(msg_val), st_rnd, st_vrnd, _v_major(st_val),
    )
    return (
        outs[0], outs[1], _v_major(outs[2]), *outs[3:7], _v_major(outs[7]),
    )
