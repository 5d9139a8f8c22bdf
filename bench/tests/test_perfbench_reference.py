"""The plain reference's checks on small hand-made histories."""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import reference as ref  # noqa: E402


def test_fnv1a_routing_matches_published_vectors():
    assert ref.fnv1a32(b"") == 0x811C9DC5
    assert ref.fnv1a32(b"a") == 0xE40C292C
    assert ref.group_of("a", 7) == 0xE40C292C % 7


def _fold(words) -> int:
    w = np.asarray(words, np.int64).reshape(-1) & 0xFFFFFFFF
    return int(sum(int(x) * (2 * i + 1) for i, x in enumerate(w)) & 0xFFFFFFFF)


def test_prefix_seals_equal_the_direct_fold():
    rng = np.random.default_rng(3)
    insts = np.arange(40, dtype=np.int32)
    values = rng.integers(-2**31, 2**31, size=(40, 4), dtype=np.int64).astype(np.int32)
    for m in (1, 17, 40):
        acc = (_fold(insts[:m]) * ref.SEAL_MIX + _fold(values[:m])) & 0xFFFFFFFF
        want = acc - (1 << 32) if acc >= 1 << 31 else acc
        assert ref.prefix_seals(insts, values, [m]) == [want]


def _row(seq: int, payload: bytes, words: int = 4) -> np.ndarray:
    raw = (np.array([seq, len(payload)], "<i4").tobytes() + payload).ljust(4 * words, b"\0")
    return np.frombuffer(raw, "<i4").copy()


def test_snapshot_check_reads_drained_rows_and_seals():
    nop = np.array([ref.NOP_SENTINEL, 0, 0, 0], np.int32)
    values = np.stack([_row(0, b"a"), nop, _row(1, b"bc"), nop])
    insts = np.arange(4, dtype=np.int32)
    log = [(0, b"a"), (2, b"bc"), (5, b"d")]
    seals = [(2, ref.prefix_seals(insts, values, [2])[0]),
             (4, ref.prefix_seals(insts, values, [4])[0])]
    assert ref.check_snapshots(insts, values, log, seals) == 0
    assert ref.check_snapshots(insts, values, log, [(4, seals[1][1] + 1)]) == 1
    assert ref.check_snapshots(insts, values, [(0, b"a"), (2, b"bX")], seals) == 1
    assert ref.check_snapshots(insts[::-1].copy(), values, log, seals) == 2


def test_submit_logs_order_and_instances():
    want = [[b"x", b"y"], [b"z"]]
    assert ref.check_submit_logs([[(0, b"x"), (1, b"y")], [(0, b"z")]], want) == 0
    assert ref.check_submit_logs([[(0, b"y"), (1, b"x")], [(0, b"z")]], want) == 2
    assert ref.check_submit_logs([[(1, b"x"), (0, b"y")], [(0, b"z")]], want) == 1
    assert ref.check_submit_logs([[(0, b"x")], [(0, b"z")]], want) == 1


def test_kv_log_puts_markers_and_counters():
    tag = ref.fnv1a32(b"client0")
    put1 = ref.put_frame(tag, 1, b"k1", b"v1")
    get2 = ref.KV_HEADER.pack(ref.KV_MAGIC, ref.KV_VERSION, ref.OP_GET, 0, tag, 2, 0, 0, 0)
    put3 = ref.put_frame(tag, 3, b"k1", b"v2")
    want = [(tag, b"k1", b"v1"), (tag, b"k1", b"v2")]
    log = [(0, put1), (1, get2), (2, put3)]
    assert ref.check_kv_log(log, want, {tag}) == 0
    assert ref.check_kv_log([(0, put1), (1, put3), (2, get2)], want, {tag}) == 1
    assert ref.check_kv_log([(0, put3), (1, put1)], want, {tag}) == 3
    assert ref.check_kv_log(log, want, {tag + 1}) == 3


def test_kv_reads_are_held_to_a_linearizable_register():
    # key 7: put 1 acked at event 3; put 2 issued at 4, acked at 9
    puts = {7: [(1, 0, 3), (2, 4, 9)]}
    ok = [
        (7, 1, 5, 6),      # before put 2 is acknowledged, the old value
        (7, 2, 5, 6),      # or the new one, issued before the read returned
        (7, 2, 10, 11),    # after put 2 is acknowledged, only the new one
    ]
    assert ref.check_kv_reads(puts, ok) == 0
    stale = [(7, 1, 10, 11)]          # put 2 was acknowledged at 9
    future = [(7, 2, 1, 2)]           # put 2 was issued at 4, after the read
    wrong_key = [(8, 1, 5, 6)]
    missing = [(7, None, 5, 6)]
    for bad in (stale, future, wrong_key, missing):
        assert ref.check_kv_reads(puts, bad) == 1


def test_kv_state_last_value_and_versions():
    puts = [(b"a", b"1"), (b"b", b"2"), (b"a", b"3")]
    assert ref.check_kv_state({b"a": (b"3", 2), b"b": (b"2", 1)}, puts) == 0
    assert ref.check_kv_state({b"a": (b"1", 2), b"b": (b"2", 1)}, puts) == 1
    assert ref.check_kv_state({b"a": (b"3", 1), b"b": (b"2", 1), b"c": (b"", 1)}, puts) == 2


def test_acked_writes_must_be_in_the_log_when_acknowledged():
    assert ref.check_acked_logged([3, -1, 5], [2, -1, 4]) == 0
    assert ref.check_acked_logged([3], [3]) == 1     # logged only later
    assert ref.check_acked_logged([3], [-1]) == 1    # never logged
