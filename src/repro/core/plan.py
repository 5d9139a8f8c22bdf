"""The cohort dispatch planner: how a round of skewed multi-group load maps
onto device dispatches (DESIGN.md §8).

Before this module the plan was smeared across ``core.api``: the fold
decision was all-or-nothing (``group_block ∈ {G, 1}``), one shared burst
size padded every cold group's chunk with NOP filler up to the hottest
group's burst, and after divergent per-group failovers the folded mapping
never re-engaged.  ``plan.py`` owns all of those decisions in one place:

* **Burst quantization** — every wire burst is a power of two in
  ``[MIN_BURST, batch]``, regardless of execution engine (Pallas kernel or
  jnp oracle).  Engine choice never shapes a burst, which is what makes the
  planner's decisions — and therefore per-group delivery logs — identical
  across the jnp/pallas × sharded/unsharded backends *and* against G
  independent single-group oracles, even under arbitrarily skewed load.
  Bounded shape vocabulary also bounds jit-cache churn.

* **Lockstep cohorts** — the enabled groups of a round partition into
  watermark-equivalence classes; groups whose quantized burst agrees ride
  one dispatch (a *tier*): hot cohorts at the full block-aligned burst,
  cold cohorts coalesced into a shared right-sized burst.  One dispatch per
  distinct burst size, so a round costs at most ``log2(batch/MIN_BURST)+1``
  dispatches however skewed the load.

* **Per-cohort fold widths** — ``fold_width_full`` generalizes the old
  binary group-folding cliff: the largest divisor ``d`` of the fold cap
  such that every ``d``-aligned block's members share one watermark (the
  kernel substitutes the block's lockstep base for non-members).
  ``cohort_blocks`` additionally *compacts* the grid over the group axis
  for the unsharded kernel path: only the blocks containing cohort members
  are visited, so a one-hot-group tier costs one group's work, not G's.

* **Realignment sweep** — after ``realign_after`` consecutive fragmented
  rounds (enabled groups spread over >1 watermark class), divergent groups
  are burned forward to a common block boundary: the skipped instances are
  never proposed and are recoverable as no-ops (paper §3.1 gap fill),
  and the full-width folded mapping re-engages.  Off by default
  (``PaxosConfig.realign_after = None``) because burning forward changes
  instance numbering relative to an independent deployment — services opt
  in when they prefer amortization over twin-exact numbering.

* **The sharded program vocabulary** — ``sharded_programs`` lists, from the
  configuration and the shard count alone, every program the groups-sharded
  dataplane may run, so that a context can compile all of them before it
  serves.  Packed dispatches run lanes per shard in powers of
  ``LANE_STEP`` (``packed_lanes``), full-width dispatches fold at the whole
  cap or not at all (``full_fold``), and every kernel dispatch visits the
  ring blocks a window at any offset needs (``sharded_window_blocks``).
"""
from __future__ import annotations

import dataclasses
from typing import Any
from collections.abc import Sequence

import numpy as np

NO_ROUND = -1
NOP_SENTINEL = -0x7FFFFFFF  # first value word marking an internal filler slot
MIN_BURST = 8               # smallest wire burst (pow2 quantization floor)
# Groups x ring slots one wire-path grid step may hold: at A <= 5 and V = 16
# a fold of GB * BB = 8192 slots is the widest that compiles for a v5e under
# the kernels' scoped-VMEM limit (GB = 64 groups at BB = 128).  Other shapes
# scale it by the words a group-slot holds in VMEM (``fold_cap``).
MAX_FOLD_LANES = 8192
_FOLD_SHAPE = (5, 16)       # the (A, V) at which MAX_FOLD_LANES compiled
# Packed sharded dispatches run 1, 8, 64, ... lanes per shard: three lane
# counts below a 256-group slab instead of eight, at the price of up to
# LANE_STEP - 1 inert pad lanes per member on the fullest shard.
LANE_STEP = 8


def wire_block(b: int) -> int:
    """Block boundary a burst of ``b`` messages advances by — the boundary
    the realignment sweep burns fragmented watermarks forward to."""
    from repro.kernels.wirepath import DEFAULT_BLOCK_B

    return min(DEFAULT_BLOCK_B, b)


def ring_block(n_instances: int) -> int:
    """Ring slots per kernel grid step (``kernels.wirepath.ring_block``)."""
    from repro.kernels.wirepath import ring_block as _ring_block

    return _ring_block(n_instances)


def window_blocks(n_instances: int, bases: Sequence[int], b: int) -> int | None:
    """Ring blocks a kernel dispatch visits per group so that every window
    ``[base, base + b)`` is covered from the block holding its first slot,
    or ``None`` when some window would wrap onto its own first block (only
    possible on rings shorter than ``b`` plus one block) — the kernel cannot
    run that window and the dispatch takes the jnp engine."""
    bb = ring_block(n_instances)
    need = max((base % bb + b for base in bases), default=b)
    nblk = -(-need // bb)
    return nblk if nblk * bb <= n_instances else None


def blocks_aligned(n_instances: int, bases: Sequence[int], b: int) -> bool:
    """True iff every window ``[base, base + b)`` starts and ends on a ring
    block boundary — the persistent wave kernel's precondition."""
    bb = ring_block(n_instances)
    return b % bb == 0 and all(base % bb == 0 for base in bases)


def _slot_words(a: int, v: int) -> int:
    """int32 words one group-slot takes in a wire-path grid step's blocks,
    inputs and outputs: the four acceptor round blocks ``(A, ·)`` pad to 8
    sublanes, the value blocks are ``(A*V, ·)`` twice and ``(V, ·)`` four
    times, and the six one-row blocks (learner delivered and inst, in and
    out; fresh; win) pad to 8 rows each."""
    pad_a = -(-a // 8) * 8
    return 2 * a * v + 4 * pad_a + 4 * v + 48


def fold_cap(
    n_groups: int, n_instances: int, n_acceptors: int, value_words: int
) -> int:
    """Widest group fold a dispatch may use: the largest divisor of
    ``n_groups`` whose grid step holds at most the VMEM of
    ``MAX_FOLD_LANES`` slots at ``_FOLD_SHAPE`` — never more slots than
    that, and proportionally fewer for more acceptors or value words."""
    budget = MAX_FOLD_LANES * _slot_words(*_FOLD_SHAPE)
    slots = min(MAX_FOLD_LANES, budget // _slot_words(n_acceptors, value_words))
    lanes = slots // ring_block(n_instances)
    return max(d for d in _divisors(n_groups) if d <= max(1, lanes))


def quantize_burst(n: int, cap: int) -> int:
    """Wire-burst sizing: next power of two >= ``n`` in [MIN_BURST, cap].

    A half-empty wire batch costs real dataplane time, so bursts right-size
    down to the load; quantizing to a bounded pow2 vocabulary keeps the jit
    cache (one compiled program per distinct shape) bounded too.
    """
    be = MIN_BURST
    while be < n:
        be *= 2
    return min(be, cap)


def burst_sizes(batch: int) -> list[int]:
    """Every burst ``quantize_burst`` can mint under ``batch``."""
    out, n = set(), 1
    while True:
        out.add(quantize_burst(n, batch))
        if n >= batch:
            return sorted(out)
        n *= 2


def packed_lanes(residency: int, groups_per_shard: int) -> int | None:
    """Lanes per shard of a packed sharded dispatch whose fullest shard
    holds ``residency`` members: the least power of ``LANE_STEP`` that
    holds them, or ``None`` -- the full-width fold -- where that would
    reach the slab's height."""
    c = 1
    while c < residency:
        c *= LANE_STEP
    return c if c < groups_per_shard else None


def full_fold(gids: Sequence[int], marks: Sequence[int], cap: int) -> int:
    """Fold width a full-width sharded kernel dispatch runs: the whole
    ``cap`` where every ``cap``-aligned block's members share a watermark,
    else 1.  Two widths per burst instead of one per divisor of ``cap``; a
    cohort that could fold at an intermediate width runs unfolded."""
    return cap if _block_lockstep(gids, marks, cap) else 1


def sharded_window_blocks(n_instances: int, b: int) -> int:
    """Ring blocks every sharded kernel dispatch of ``b``-slot windows
    visits: as many as a window at any offset within its first block needs
    (the kernels' own default), so that the count follows from the burst
    alone.  A window that fits in fewer blocks visits one more, loaded and
    stored back unchanged."""
    bb = ring_block(n_instances)
    return min(n_instances // bb, -(-b // bb) + 1)


@dataclasses.dataclass(frozen=True)
class ShardedProgram:
    """One program of the groups-sharded dataplane: a ``packed`` dispatch
    of ``width`` lanes per shard, or a ``full``-width one folding ``width``
    groups per grid step; ``window_blocks`` is ``None`` on the jnp
    engine."""

    kind: str
    window_blocks: int | None
    width: int
    burst: int


def sharded_programs(cfg: Any, n_shards: int, kernels: bool) -> list[ShardedProgram]:
    """Every program the groups-sharded dataplane may run for ``cfg``
    (a ``PaxosConfig``) over ``n_shards`` shards, on the kernel engine or
    the jnp one.  Packing and the packed/full-width crossover choose only
    from this list.  A kernel dataplane falls back to the jnp engine for a
    window that would wrap onto its own first ring block, which only a ring
    shorter than a burst plus one block allows."""
    n, gl = cfg.n_instances, cfg.n_groups // n_shards
    bb = ring_block(n)
    lanes = [c for c in (LANE_STEP**k for k in range(gl.bit_length())) if c < gl]
    out = []
    for b in burst_sizes(cfg.batch):
        engines: list[int | None] = [None]
        if kernels:
            engines = [sharded_window_blocks(n, b)]
            if bb - 1 + b > n:
                engines.append(None)
        for nblk in engines:
            cap = fold_cap(gl, n, cfg.n_acceptors, cfg.value_words)
            folds = sorted({1, cap}) if nblk is not None else [1]
            out += [ShardedProgram("packed", nblk, c, b) for c in lanes]
            out += [ShardedProgram("full", nblk, gb, b) for gb in folds]
    return out


def _divisors(cap: int) -> list[int]:
    return [d for d in range(1, cap + 1) if cap % d == 0]


def _block_lockstep(gids: Sequence[int], marks: Sequence[int], d: int) -> bool:
    """True iff every ``d``-aligned block's members (of ``gids``) share one
    watermark — the validity condition for folding ``d`` groups per grid
    step with cohort-base substitution for non-members."""
    classes: dict[int, int] = {}
    for g in gids:
        blk = g // d
        if classes.setdefault(blk, marks[g]) != marks[g]:
            return False
    return True


def fold_width_full(
    gids: Sequence[int], marks: Sequence[int], cap: int
) -> int:
    """Fold width for a *full-width* dispatch (every group block on the
    grid): the largest divisor of ``cap`` folding validly over ``gids``.

    Generalizes the historical ``group_block ∈ {cap, 1}`` cliff: cohorts
    that diverged after per-group failovers can still fold block-wise
    (e.g. groups [0..3] at one watermark and [4..7] at another fold at
    width 4), each block deriving its ring offset from its own lockstep
    base."""
    for d in sorted(_divisors(cap), reverse=True):
        if _block_lockstep(gids, marks, d):
            return d
    return 1


def cohort_blocks(
    gids: Sequence[int], marks: Sequence[int], cap: int
) -> tuple[int, list[int]]:
    """Group-axis *compaction* for a cohort dispatch: pick ``(gb, blocks)``
    so the kernel grid visits only the aligned ``gb``-blocks containing
    cohort members.

    Objective: minimize the number of visited blocks (grid steps along the
    group axis), then the fold width (block size — smaller blocks carry
    fewer inert filler rows).  A single hot group therefore costs one
    1-group block; a 7-of-8 cold cohort costs one folded 8-group block."""
    best: tuple[tuple[int, int], int, list[int]] | None = None
    for d in _divisors(cap):
        if not _block_lockstep(gids, marks, d):
            continue
        blocks = sorted({g // d for g in gids})
        key = (len(blocks), d)
        if best is None or key < best[0]:
            best = (key, d, blocks)
    assert best is not None  # d = 1 is always valid
    return best[1], best[2]


def pack_rows(
    rows: Sequence[np.ndarray], be: int, value_words: int
) -> tuple[np.ndarray, np.ndarray]:
    """Pack encoded value rows into a ``(be, V)`` wire burst; unfilled
    slots carry the NOP sentinel and are inactive.

    Validated up front: an oversized chunk must fail *before* any wire
    array is built, never mid-write — the historical unguarded loop raised
    a bare ``IndexError`` after partially mutating the burst."""
    if len(rows) > be:
        raise ValueError(
            f"chunk of {len(rows)} rows exceeds quantized burst {be}"
        )
    vals = np.zeros((be, value_words), np.int32)
    active = np.zeros((be,), bool)
    vals[:, 0] = NOP_SENTINEL
    for j, row in enumerate(rows):
        vals[j] = row
        active[j] = True
    return vals, active


def scatter_rows(
    gids: Sequence[int],
    values: np.ndarray,
    active: np.ndarray | None,
    g: int,
    value_words: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Scatter compact cohort rows into a full-width ``(G, BE, V)`` burst:
    non-member rows carry the NOP sentinel and are inactive (they ride any
    dispatch inert).  The single definition of the full-width packing
    convention, shared by the jnp-oracle and sharded execution paths."""
    be = values.shape[1]
    vals_f = np.zeros((g, be, value_words), np.int32)
    vals_f[:, :, 0] = NOP_SENTINEL
    act_f = np.zeros((g, be), bool)
    for row, gid in enumerate(gids):
        vals_f[gid] = values[row]
        if active is not None:
            act_f[gid] = active[row]
    return vals_f, act_f


@dataclasses.dataclass(frozen=True)
class Cohort:
    """One dispatch of a round plan: the enabled groups sharing a quantized
    burst size.  ``gids`` may span several watermark classes — the dispatch
    folds block-wise where classes align and degrades to width-1 blocks
    where they don't (``fold_width_full`` / ``cohort_blocks``).

    ``rounds`` > 1 marks a *persistent wave* (DESIGN.md §11): the dispatch
    runs that many back-to-back full-batch Phase-2 rounds device-side,
    consuming ``rounds`` burst-sized chunks per member, and syncs results
    back to the host once."""

    gids: tuple[int, ...]
    burst: int
    rounds: int = 1


@dataclasses.dataclass(frozen=True)
class RoundPlan:
    """The resolved plan for one chunk wave.

    ``cohorts`` are ordered hot -> cold (burst descending); ``realign``
    lists ``(gid, target_watermark)`` burns the dataplane must apply before
    dispatching; ``fragmentation`` counts watermark classes among enabled
    groups (after burns); ``full_fold`` marks the highest-amortization
    state — one cohort, one watermark class — where the dispatch folds the
    full width."""

    cohorts: tuple[Cohort, ...]
    enabled: tuple[bool, ...]
    realign: tuple[tuple[int, int], ...]
    fragmentation: int
    full_fold: bool


@dataclasses.dataclass(frozen=True)
class PlacementMap:
    """Load-weighted group -> shard placement for the sharded dataplane
    (DESIGN.md §13): a permutation ``slot_of[gid] -> slot`` where slot
    ``s * Gl + r`` is physical slab row ``r`` on mesh shard ``s``.

    Device slabs are *slot*-indexed; group identity (and therefore session
    routing hashes, log segment names and twin-oracle numbering) never
    changes when a group moves — only its slot does.  The map is a plain
    permutation so membership events compose with placement: every group id,
    live or free, always owns exactly one slot, and a migration is a slot
    swap between a live group and a free one.

    Construction is deterministic and engine-agnostic: ``weighted`` is an
    LPT greedy over ``(-load, gid)`` with ties broken by (shard load sum,
    occupancy, shard id), so equal loads round-robin ``gid i -> shard
    i % n_shards`` and all four backends resolve the identical map from the
    identical ``group_loads()`` snapshot.
    """

    slot_of: tuple[int, ...]
    groups_per_shard: int

    def __post_init__(self) -> None:
        n = len(self.slot_of)
        if n % self.groups_per_shard:
            raise ValueError(
                f"{n} groups not divisible by Gl={self.groups_per_shard}"
            )
        if sorted(self.slot_of) != list(range(n)):
            raise ValueError(f"slot_of is not a permutation: {self.slot_of}")

    @property
    def n_groups(self) -> int:
        return len(self.slot_of)

    @property
    def n_shards(self) -> int:
        return len(self.slot_of) // self.groups_per_shard

    @property
    def group_of(self) -> tuple[int, ...]:
        """Inverse permutation: physical slot -> group id."""
        inv = [0] * len(self.slot_of)
        for gid, slot in enumerate(self.slot_of):
            inv[slot] = gid
        return tuple(inv)

    def shard_of(self, gid: int) -> int:
        return self.slot_of[gid] // self.groups_per_shard

    def row_of(self, gid: int) -> int:
        """Local slab row of ``gid`` within its owning shard."""
        return self.slot_of[gid] % self.groups_per_shard

    def identity_map(self) -> bool:
        return all(s == g for g, s in enumerate(self.slot_of))

    def swapped(self, gid: int, other: int) -> "PlacementMap":
        """The map with ``gid`` and ``other`` exchanging slots — the one
        placement mutation migration performs (both identities keep exactly
        one slot, so the result is again a permutation by construction)."""
        slots = list(self.slot_of)
        slots[gid], slots[other] = slots[other], slots[gid]
        return PlacementMap(tuple(slots), self.groups_per_shard)

    @classmethod
    def identity(cls, n_groups: int, groups_per_shard: int) -> "PlacementMap":
        return cls(tuple(range(n_groups)), groups_per_shard)

    @classmethod
    def weighted(
        cls,
        loads: Sequence[int],
        n_shards: int,
        groups_per_shard: int,
    ) -> "PlacementMap":
        """LPT greedy: heaviest group first onto the least-loaded non-full
        shard.  Ragged by construction — a hot shard may host one tenant
        while a cold shard hosts ``Gl`` — subject only to the ``Gl``-slot
        capacity.  Within a shard, rows fill in assignment order."""
        g = len(loads)
        if g != n_shards * groups_per_shard:
            raise ValueError(
                f"{g} loads for {n_shards} x {groups_per_shard} slots"
            )
        order = sorted(range(g), key=lambda i: (-int(loads[i]), i))
        sums = [0] * n_shards
        rows: list[list[int]] = [[] for _ in range(n_shards)]
        for gid in order:
            s = min(
                (s for s in range(n_shards) if len(rows[s]) < groups_per_shard),
                key=lambda s: (sums[s], len(rows[s]), s),
            )
            sums[s] += int(loads[gid])
            rows[s].append(gid)
        slots = [0] * g
        for s in range(n_shards):
            for r, gid in enumerate(rows[s]):
                slots[gid] = s * groups_per_shard + r
        return cls(tuple(slots), groups_per_shard)


class DispatchPlanner:
    """Owns the per-round dispatch policy for a multi-group context.

    Stateless per round except for the realignment counter (consecutive
    fragmented rounds) and introspection stats; the plan itself is a pure
    function of host-authoritative scalars (loads, watermark mirrors,
    membership, rounds), which is why unsharded, sharded and the jnp oracle
    resolve every round identically — the parity contract (DESIGN.md §8).
    """

    def __init__(
        self,
        batch: int,
        n_instances: int,
        realign_after: int | None = None,
        persistent_rounds: int = 1,
        sharded: bool = False,
    ) -> None:
        self.batch = batch
        self.n_instances = n_instances
        self.realign_after = realign_after
        self.persistent_rounds = max(1, int(persistent_rounds))
        # the sharded engine executes a K-round wave as K cohort dispatches
        # (DESIGN.md §11's documented fallback); the PLANNER owns that
        # clamp so ``persistent_waves`` telemetry counts only waves that
        # actually ran device-persistent, instead of the dispatch layer
        # silently unrolling K > 1 cohorts after they were counted
        self.sharded = sharded
        self._fragmented_rounds = 0
        self.last_plan: RoundPlan | None = None
        self.stats: dict[str, Any] = {
            "rounds": 0,
            "dispatches": 0,
            "full_fold_rounds": 0,
            "realignments": 0,
            "persistent_waves": 0,
            "burst_shapes": set(),
            "service_loads": None,
        }

    # -- bookkeeping hooks ---------------------------------------------------
    def note_burst(self, be: int) -> None:
        """Record a burst shape minted outside plan_round (staged paths)."""
        self.stats["burst_shapes"].add(be)

    def observe_service_loads(self, loads: Sequence[int]) -> None:
        """Serving-tier load snapshot (``ConsensusService.group_loads``) —
        introspection only; tiering uses per-wave queue depths so that the
        plan stays a pure function of the round's inputs."""
        self.stats["service_loads"] = list(loads)

    def report(self) -> dict[str, Any]:
        # Snapshot-copy every mutable value: a report is an observation,
        # not a window onto live planner state (callers mutating a report
        # must not perturb planning, and later observe_service_loads calls
        # must not rewrite already-returned reports).
        out = dict(self.stats)
        out["burst_shapes"] = sorted(self.stats["burst_shapes"])
        loads = self.stats["service_loads"]
        out["service_loads"] = None if loads is None else list(loads)
        out["fragmented_rounds"] = self._fragmented_rounds
        out["realign_after"] = self.realign_after
        return out

    def _wave_depth(
        self,
        burst: int,
        gids: Sequence[int],
        pending: Sequence[int] | None,
        marks: Sequence[int],
    ) -> int:
        """Persistent-wave depth K for one cohort (DESIGN.md §11).

        K > 1 only when the burst is the full batch — the wave's rounds are
        consecutive batch-sized queue slices, so numbering is identical to
        K single-round waves by construction — every member has K full
        chunks queued, and every member's rounds start and end on a ring
        block (``blocks_aligned``: the persistent kernel's precondition,
        applied on every engine so that all of them plan the same waves).
        Clamped by the ``persistent_rounds`` policy knob and
        by the ring (a wave may not lap itself: K * burst <= N).  On a
        sharded planner K is clamped to 1 up front: the wave would unroll
        into K cohort dispatches anyway (host-authoritative control scalars
        enter every dispatch), so minting K > 1 would only inflate the
        ``persistent_waves`` stat."""
        if (
            self.sharded
            or self.persistent_rounds <= 1
            or pending is None
            or burst != self.batch
            or not blocks_aligned(
                self.n_instances, [marks[i] for i in gids], burst
            )
        ):
            return 1
        k = min(pending[i] // burst for i in gids)
        k = min(k, self.persistent_rounds, self.n_instances // burst)
        return max(1, k)

    # -- the planner ---------------------------------------------------------
    def plan_round(
        self,
        loads: Sequence[int],
        marks: Sequence[int],
        live: Sequence[bool],
        crnd: Sequence[int],
        pending: Sequence[int] | None = None,
    ) -> RoundPlan:
        """Resolve one chunk wave: membership/frozen masking, the
        realignment sweep, and the hot->cold cohort tiering.

        ``loads`` are this wave's per-group chunk lengths; ``marks`` the
        host watermark mirrors; ``live`` membership; ``crnd`` the host
        round mirrors (``NO_ROUND`` = frozen under a software coordinator).
        ``pending`` gives per-group *total* queued lengths (first chunk
        included); when provided and ``persistent_rounds`` > 1, a cohort
        whose burst is the full batch and whose every member has K full
        batch-sized chunks queued at a ring-block-aligned watermark is
        planned as a K-round persistent wave
        — burst quantization itself never changes, so engine-agnostic
        numbering is preserved round for round.
        """
        g = len(loads)
        enabled = tuple(
            loads[i] > 0 and bool(live[i]) and crnd[i] != NO_ROUND
            for i in range(g)
        )
        en_gids = [i for i in range(g) if enabled[i]]
        marks = list(marks)

        # A round is *fragmented* when it cannot run the highest-amortization
        # mapping: enabled watermarks spread over >1 class (fold breaks), OR
        # some enabled watermark off the full-batch block boundary (the
        # kernel window alignment a quantized sub-batch burst can cost —
        # engine-agnostic on purpose: the burn must fire identically on the
        # jnp oracle or backends' instance numbering would fork).
        bb = wire_block(self.batch)
        classes = {marks[i] for i in en_gids}
        fragmented = len(classes) > 1 or any(
            marks[i] % bb for i in en_gids
        )
        if fragmented:
            self._fragmented_rounds += 1
        elif en_gids:
            self._fragmented_rounds = 0

        realign: list[tuple[int, int]] = []
        if (
            self.realign_after is not None
            and fragmented
            and self._fragmented_rounds >= self.realign_after
        ):
            # burn every straggling enabled group forward to one common
            # block boundary: the skipped instances are never proposed and
            # are recoverable as no-ops (paper §3.1), and the full-width
            # folded block-aligned mapping re-engages on the next dispatch
            target = -(-max(classes) // bb) * bb
            for i in en_gids:
                if marks[i] != target:
                    realign.append((i, target))
                    marks[i] = target
            self._fragmented_rounds = 0
            self.stats["realignments"] += 1

        tiers: dict[int, list[int]] = {}
        for i in en_gids:
            be = quantize_burst(loads[i], self.batch)
            tiers.setdefault(be, []).append(i)
            self.stats["burst_shapes"].add(be)
        cohorts = tuple(
            Cohort(
                gids=tuple(gids),
                burst=be,
                rounds=self._wave_depth(be, gids, pending, marks),
            )
            for be, gids in sorted(tiers.items(), reverse=True)
        )
        if any(c.rounds > 1 for c in cohorts):
            self.stats["persistent_waves"] += 1
        fragmentation = len({marks[i] for i in en_gids})
        plan = RoundPlan(
            cohorts=cohorts,
            enabled=enabled,
            realign=tuple(realign),
            fragmentation=fragmentation,
            full_fold=len(cohorts) == 1 and fragmentation == 1,
        )
        self.stats["rounds"] += 1
        self.stats["dispatches"] += len(cohorts)
        if plan.full_fold:
            self.stats["full_fold_rounds"] += 1
        self.last_plan = plan
        return plan
