"""Bytes the consensus wire path needs, computed from dispatch shapes.

One dispatch advances each member group's ``rounds`` x ``burst``-slot
window of the instance ring.  What it has to move, at the kernel's block
granularity (128 ring slots, or the whole ring when 128 does not tile it):

* each visited ring block of every acceptor's register file -- the
  promised round, the voted round and the ``V`` value words of each slot --
  read and written back;
* each visited block of the learner's dedup ring -- delivered flag,
  decided instance and ``V`` value words -- read and written back;
* the burst in (``V`` value words and the active flag per lane) and the
  result out (fresh flag, instance and ``V`` value words per lane).

Nothing else counts: a kernel that moves more than this (whole slabs,
relayout copies, padding lanes) reads below its roofline.
"""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
BLOCK = 128
WORD = 4


def ring_block(n_instances: int) -> int:
    return BLOCK if n_instances % BLOCK == 0 else n_instances


def wirepath_bytes(paxos: dict, dispatches) -> int:
    """Bytes needed by ``dispatches``, each ``(burst, rounds, marks)``
    with ``marks`` the member groups' sequencer marks at dispatch."""
    a, v, n = paxos["n_acceptors"], paxos["value_words"], paxos["n_instances"]
    bb = ring_block(n)
    slot = (2 + v) * WORD
    total = 0
    for burst, rounds, marks in dispatches:
        span = burst * rounds
        for m in marks:
            blocks = min(-(-(m % bb + span) // bb), n // bb)
            total += blocks * bb * slot * (a + 1) * 2
        lanes = len(marks) * span
        total += lanes * (v + 1) * WORD + lanes * (v + 2) * WORD
    return total


def peak(device_kind: str) -> dict:
    """The device's published peaks; an unknown device is an error."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]
