"""The general traffic generator: a mix file's parameters and a seed give a
schedule of operations.

Every seed draws the same multiset of work -- the same inter-arrival gaps,
the same count of each operation kind, the same key and session ranks --
and the seed only chooses their order and which key id each key rank
names.  So two seeds offer the same load, shuffled, and the spread between
runs measures the system rather than the draw.  A closed loop's clients
are bound to sessions by the same stratified draw: a hot session holds
more of the clients, and each group keeps the same number of ops
outstanding for the whole run.

A mix file (``bench/traffic/<mix>.json``) holds::

    {"generator": "generator",            # this module
     "loop": "open" | "closed",
     "rate_per_s": 8000,                  # open loop: Poisson arrivals
     "population": 1024,                  # closed loop: clients, one op each
     "ops": {"get": 0.5, "put": 0.5},     # or {"submit": 1.0}
     "keys": {"dist": "zipfian", "theta": 0.99},      # KV ops only
     "sessions": {"count": 16, "dist": "uniform"},    # or zipfian + theta
     "payload_bytes": 56}                 # raw submits

Zipfian ranks follow YCSB's core generator (rank r drawn with weight
``1 / (r + 1) ** theta``); key ranks are scrambled to key ids by a seeded
permutation, as YCSB's scrambled zipfian spreads hot keys.  Session ranks
are not scrambled: session ``r`` is always the ``r``-th hottest, so the
load each consensus group receives is the same for every seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

KIND_GET, KIND_PUT, KIND_SUBMIT = 0, 1, 2
KINDS = {"get": KIND_GET, "put": KIND_PUT, "submit": KIND_SUBMIT}


@dataclasses.dataclass
class Schedule:
    """One phase's operations, in issue order.

    ``due`` holds each op's due time in seconds from the phase start (open
    loop).  A closed loop has no due times: its entries are the
    ``population`` clients, each bound to one session, each keeping one op
    outstanding and issuing its next as soon as the last is acknowledged."""

    loop: str
    kind: np.ndarray                 # int8, KIND_*
    key: np.ndarray                  # int32 key id, -1 where unused
    session: np.ndarray              # int32 session rank
    due: np.ndarray | None = None    # float64 seconds
    population: int = 0

    def __len__(self) -> int:
        return len(self.kind)


def stratified_zipf(n_items: int, theta: float, count: int) -> np.ndarray:
    """``count`` ranks in ``[0, n_items)`` whose histogram follows the
    zipfian weights as closely as whole counts allow: the inverse CDF read
    at the quantiles ``(j + 0.5) / count``.  Sorted; shuffle before use."""
    w = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w / w.sum())
    q = (np.arange(count, dtype=np.float64) + 0.5) / count
    return np.minimum(np.searchsorted(cdf, q), n_items - 1).astype(np.int32)


def stratified_exponential(rate: float, count: int) -> np.ndarray:
    """``count`` inter-arrival gaps of a Poisson process at ``rate``: the
    exponential's quantiles ``(j + 0.5) / count``.  Sorted; shuffle."""
    q = (np.arange(count, dtype=np.float64) + 0.5) / count
    return -np.log1p(-q) / rate


def _ranks(spec: dict, count: int) -> np.ndarray:
    n = int(spec["count"])
    if spec.get("dist", "uniform") == "zipfian":
        return stratified_zipf(n, float(spec["theta"]), count)
    return (np.arange(count, dtype=np.int64) % n).astype(np.int32)


def _kinds(ops: dict, count: int) -> np.ndarray:
    names = sorted(ops)
    shares = np.array([float(ops[k]) for k in names])
    cuts = np.floor(np.cumsum(shares / shares.sum()) * count + 0.5).astype(int)
    out = np.empty(count, np.int8)
    lo = 0
    for name, hi in zip(names, cuts, strict=True):
        out[lo:hi] = KINDS[name]
        lo = hi
    return out


def schedule(mix: dict, seed: int, seconds: float, n_keys: int = 0) -> Schedule:
    """The schedule of one phase of ``seconds`` (open loop), or the
    clients of a closed loop, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        count = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    elif mix["loop"] == "closed":
        count = int(mix["population"])
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    kind = rng.permutation(_kinds(mix["ops"], count))
    session = rng.permutation(_ranks(mix["sessions"], count))
    key = np.full(count, -1, np.int32)
    if "keys" in mix:
        keys = mix["keys"]
        if keys.get("dist", "uniform") == "zipfian":
            ranks = stratified_zipf(n_keys, float(keys["theta"]), count)
        else:
            ranks = (np.arange(count) % n_keys).astype(np.int32)
        key = rng.permutation(n_keys).astype(np.int32)[rng.permutation(ranks)]
    if mix["loop"] == "closed":
        return Schedule("closed", kind, key, session, None, int(mix["population"]))
    gaps = rng.permutation(stratified_exponential(float(mix["rate_per_s"]), count))
    # the same gaps for every seed sum to the same span; scale it to end
    # one mean gap before the phase closes
    due = np.cumsum(gaps) * (seconds * count / (count + 1) / gaps.sum())
    return Schedule("open", kind, key, session, due)
