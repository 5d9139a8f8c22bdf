"""The plain reference that decides ``correct``.

It imports nothing of the program.  It knows the system's documented wire
formats (the KV op frame of DESIGN.md section 10, the ``(seq, len,
payload)`` value framing, the NOP filler word, the snapshot seal's fold),
the FNV-1a session routing, and the operations the harness issued, and it
checks what the timed run produced against them:

* every group's decided log holds exactly the ops routed to it, in the
  order they were submitted (KV read-index markers aside);
* every acknowledged op was in its group's log when it was acknowledged;
* every KV get returned a value that a linearizable register could have
  returned, given when each put was issued and acknowledged;
* the final KV state equals the puts applied in submission order;
* every snapshot's drained ring prefix is the decided history, and its
  seal equals the plain digest of that prefix.

Each check returns a count of violations; every limit is 0.
"""
from __future__ import annotations

import struct

import numpy as np

NOP_SENTINEL = -0x7FFFFFFF          # first value word of a filler slot
SEAL_MIX = 1000003                  # leaf fold constant of the seal
KV_HEADER = struct.Struct("<BBBBIIHHH")
KV_MAGIC, KV_VERSION, OP_PUT, OP_GET = 0xC5, 1, 1, 4
_M32 = 0xFFFFFFFF


def fnv1a32(data: bytes) -> int:
    h = 0x811C9DC5
    for byte in data:
        h = ((h ^ byte) * 0x01000193) & _M32
    return h


def group_of(session_id: str, n_groups: int) -> int:
    """Session routing with every group live: FNV-1a of the id, mod G."""
    return fnv1a32(session_id.encode()) % n_groups


def put_frame(tag: int, counter: int, key: bytes, value: bytes) -> bytes:
    return KV_HEADER.pack(KV_MAGIC, KV_VERSION, OP_PUT, 0, tag, counter,
                          len(key), len(value), 0) + key + value


def parse_frame(buf: bytes) -> tuple | None:
    """``(opcode, sid_tag, counter, key, value)`` of a well-formed KV
    frame, else ``None``."""
    if len(buf) < KV_HEADER.size:
        return None
    magic, ver, op, flags, tag, counter, klen, vlen, elen = KV_HEADER.unpack_from(buf)
    if magic != KV_MAGIC or ver != KV_VERSION or flags or elen:
        return None
    if len(buf) != KV_HEADER.size + klen + vlen:
        return None
    key = buf[KV_HEADER.size:KV_HEADER.size + klen]
    return op, tag, counter, key, buf[KV_HEADER.size + klen:]


# ---------------------------------------------------------------------------
# the seal: a weighted fold of int32 words, two leaves mixed
# ---------------------------------------------------------------------------
def _prefix_fold(words: np.ndarray) -> np.ndarray:
    """``out[m]`` = fold of the first ``m`` words, mod 2**32."""
    bits = words.reshape(-1).astype(np.int64) & _M32
    w = 2 * np.arange(bits.size, dtype=np.int64) + 1
    terms = (bits.astype(np.uint64) * w.astype(np.uint64)) & np.uint64(_M32)
    out = np.zeros(bits.size + 1, np.uint64)
    np.cumsum(terms, out=out[1:])
    return out & np.uint64(_M32)


def _signed(x: int) -> int:
    x &= _M32
    return x - (1 << 32) if x >= 1 << 31 else x


def prefix_seals(insts: np.ndarray, values: np.ndarray, marks: list[int]) -> list[int]:
    """The seal over the first ``m`` drained entries, for each ``m``."""
    if not marks:
        return []
    fi = _prefix_fold(insts)
    fv = _prefix_fold(values)
    width = values.shape[1] if values.ndim == 2 else 0
    out = []
    for m in marks:
        if m == 0:
            out.append(0)
            continue
        acc = (int(fi[m]) * SEAL_MIX + int(fv[m * width])) & _M32
        out.append(_signed(acc))
    return out


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------
def check_submit_logs(logs: list[list], expected: list[list[bytes]]) -> int:
    """Raw-submit cells: each group's log payloads equal the payloads
    routed to it, in submission order, and instances ascend."""
    bad = 0
    for log, want in zip(logs, expected, strict=True):
        got = [p for _i, p in log]
        bad += abs(len(got) - len(want))
        bad += sum(1 for a, b in zip(got, want, strict=False) if a != b)
        bad += _non_ascending(log)
    return bad


def _non_ascending(log: list) -> int:
    insts = [i for i, _p in log]
    return sum(1 for a, b in zip(insts, insts[1:], strict=False) if b <= a)


def check_kv_log(log: list, expected_puts: list[tuple[int, bytes, bytes]],
                 tags: set[int]) -> int:
    """KV cells: the log's put frames are the puts issued, in submission
    order, with their sessions' tags; every other frame is a read-index
    marker of a known session; each session's counters ascend."""
    bad = _non_ascending(log)
    puts = []
    last: dict[int, int] = {}
    for _inst, buf in log:
        f = parse_frame(buf)
        if f is None:
            bad += 1
            continue
        op, tag, counter, key, value = f
        if counter <= last.get(tag, 0) or tag not in tags:
            bad += 1
        last[tag] = counter
        if op == OP_PUT:
            puts.append((tag, key, value))
        elif op != OP_GET or key or value:
            bad += 1
    bad += abs(len(puts) - len(expected_puts))
    bad += sum(1 for a, b in zip(puts, expected_puts, strict=False) if a != b)
    return bad


def check_acked_logged(ack_pos: list[int], log_pos: list[int]) -> int:
    """Every acknowledged op sits in its group's log below the log length
    seen when it was acknowledged.  ``log_pos`` is -1 for an op the log
    lacks."""
    return sum(1 for a, p in zip(ack_pos, log_pos, strict=True)
               if a >= 0 and not 0 <= p < a)


def check_kv_reads(puts_by_key: dict, gets: list) -> int:
    """Register linearizability with the write order fixed to submission
    order (which ``check_kv_log`` holds the log to).

    ``puts_by_key[k]`` lists ``(op_id, issue_seq, ack_seq)`` in submission
    order; each get is ``(key, answer_op_id or None, issue_seq, ack_seq)``.
    A get may return put ``j`` iff ``j`` was issued before the get
    returned and no later put to the key was acknowledged before the get
    was issued."""
    index: dict[int, tuple[int, int]] = {}
    suffix_min: dict[int, list[int]] = {}
    for k, puts in puts_by_key.items():
        acks = [a for _o, _i, a in puts]
        sm = [0] * (len(acks) + 1)
        sm[-1] = 1 << 62
        for j in range(len(acks) - 1, -1, -1):
            sm[j] = min(acks[j], sm[j + 1])
        suffix_min[k] = sm
        for j, (op, _i, _a) in enumerate(puts):
            index[op] = (k, j)
    bad = 0
    for key, answer, g_issue, g_ack in gets:
        hit = index.get(answer) if answer is not None else None
        if hit is None or hit[0] != key:
            bad += 1
            continue
        j = hit[1]
        _op, p_issue, _p_ack = puts_by_key[key][j]
        if p_issue >= g_ack or suffix_min[key][j + 1] < g_issue:
            bad += 1
    return bad


def check_kv_state(state: dict, puts_in_order: list[tuple[bytes, bytes]]) -> int:
    """The replica's ``key -> (value, version)`` equals the puts applied
    in submission order: the last value, and one version per put."""
    want: dict[bytes, tuple[bytes, int]] = {}
    for key, value in puts_in_order:
        want[key] = (value, want.get(key, (b"", 0))[1] + 1)
    bad = sum(1 for k, v in want.items() if state.get(k) != v)
    return bad + sum(1 for k in state if k not in want)


def check_snapshots(
    insts: np.ndarray, values: np.ndarray, log: list,
    seals: list[tuple[int, int]],
) -> int:
    """One group's drained prefix: contiguous instances from 0, its
    non-filler rows decode to the log's leading entries, and each recorded
    ``(watermark, seal)`` matches the plain fold of the prefix."""
    if not seals:
        return 0
    wm = max(m for m, _s in seals)
    bad = 0
    if insts.size != wm or not np.array_equal(insts, np.arange(wm, dtype=insts.dtype)):
        return len(seals)
    drained = []
    seen: set[int] = set()
    for inst, row in zip(insts.tolist(), values, strict=True):
        if int(row[0]) == NOP_SENTINEL:
            continue
        seq, n = int(row[0]), int(row[1])
        if seq in seen:
            continue
        seen.add(seq)
        drained.append((inst, row.astype("<i4").tobytes()[8:8 + n]))
    bad += sum(1 for a, b in zip(drained, log, strict=False) if a != b)
    bad += sum(1 for _inst, _p in log[len(drained):] if _inst < wm)
    bad += len(drained) - min(len(drained), len(log))
    want = prefix_seals(insts, values, [m for m, _s in seals])
    bad += sum(1 for (_m, s), w in zip(seals, want, strict=True) if s != w)
    return bad
