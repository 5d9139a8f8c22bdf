"""Drive one cell of the benchmark: set-up, warm-up, the measured window,
the drain, the reference checks and the metrics.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<mix>.json``, whose ``generator`` names a module under
``bench/traffic/``); each per-layer metric has a reader of its own under
``bench/metrics/``.  Everything is found by name, so a new cell, mix or
metric is a new file.

The client is one thread that drives the served path as a user would:
``KVSession.put``/``get`` or ``Session.submit``, then
``ConsensusService.pump``.  An open loop issues each op at its due time
(late if the previous pump ran long) and times it from that due time; a
closed loop keeps a fixed population outstanding.  Ops are acknowledged
when the client sees them: a put once it is delivered and applied (after
the pump and ``ReplicatedKV.refresh``), a raw submit once delivered, a get
when it returns.  Between pumps the client compacts as an operator would:
a group whose undrained ring span reaches half the ring is snapshotted.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time

import numpy as np

from . import reference as ref
from .system import System
from .traffic.generator import KIND_GET, KIND_PUT, KIND_SUBMIT

BENCH = os.path.dirname(os.path.abspath(__file__))
WARM_SEED = 0x5EED_0F_A11
WARM_CHUNK_S = 1.0
WARM_LOADS = (1, 2, 4)
STALL_S = 0.05
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def benchmark(bench_dir: str = BENCH) -> dict:
    with open(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json")) as f:
        return json.load(f)


def _json(bench_dir: str, kind: str, name: str) -> dict:
    with open(os.path.join(bench_dir, kind, f"{name}.json")) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object
    end_to_end: list
    per_layer: list


def load_cell(name: str, bench_dir: str = BENCH) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration, its
    mix, its mix's generator, and the metrics it reports."""
    bm = benchmark(bench_dir)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    traffic = _json(bench_dir, "traffic", w["traffic"])
    gen = load_module(
        os.path.join(bench_dir, "traffic", f"{traffic['generator']}.py"),
        f"bench_traffic_{traffic['generator']}",
    )
    e2e = [m for m in bm["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bm["per_layer"]
        if name in m.get("workloads", [name] if m["moves"] in e2e_names else [])
    ]
    return Cell(name, int(w["chips"]), _json(bench_dir, "configs", w["config"]),
                traffic, gen, e2e, per_layer)


def metric_reader(name: str, bench_dir: str = BENCH):
    """The reader of per-layer metric ``name``: ``bench/metrics/<name>.py``,
    or, for a name split by the end-to-end metric it moves
    (``pump_ms_per_kop.lat``), the file of the part before the first dot."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(bench_dir, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return load_module(path, f"bench_metric_{stem}").read
    raise FileNotFoundError(f"no reader for metric {name!r} under {bench_dir}/metrics")


# ---------------------------------------------------------------------------
# host spans (traced runs only)
# ---------------------------------------------------------------------------
class Spans:
    """Host-clock spans around calls into the program's layers, with self
    time (a span's duration less its nested spans'), each also written to
    the profiler trace as a ``TraceAnnotation`` of the same name."""

    def __init__(self) -> None:
        import jax

        self._annotate = jax.profiler.TraceAnnotation
        self.stats: dict[str, list] = {}
        self._stack: list[list] = []

    def reset(self) -> None:
        self.stats = {}

    def wrap(self, obj, attr: str, name: str, on_call=None) -> None:
        orig = getattr(obj, attr)
        annotate, stack = self._annotate, self._stack

        def spanned(*args, **kwargs):
            if on_call is not None:
                on_call(args)
            frame = [0.0]
            stack.append(frame)
            t0 = time.perf_counter()
            try:
                with annotate(name):
                    return orig(*args, **kwargs)
            finally:
                dt = time.perf_counter() - t0
                stack.pop()
                if stack:
                    stack[-1][0] += dt
                s = self.stats.setdefault(name, [0.0, 0.0, 0])
                s[0] += dt
                s[1] += dt - frame[0]
                s[2] += 1

        setattr(obj, attr, spanned)

    def region(self, name: str):
        return self._annotate(name)


class _NoSpans:
    def region(self, _name: str):
        return _NULL


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *_):
        return False


_NULL = _Null()


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------
class Client:
    """One client thread driving the served path, with the history the
    reference needs: for every op, its kind, target, issue and
    acknowledgement (as event sequence numbers and host times)."""

    def __init__(self, sysm: System, config: dict, mix: dict, seed: int,
                 control: bool = False, spans=None):
        self.sys = sysm
        self.kv_mode = sysm.kv is not None
        self.control = control
        self.spans = spans or _NoSpans()
        self.n_keys = int(config.get("kv", {}).get("keys", 0))
        self.keys = [b"k%07d" % i for i in range(self.n_keys)]
        n_sessions = int(mix["sessions"]["count"])
        prefix = "client" if self.kv_mode else "tenant"
        self.session_ids = [f"{prefix}{r}" for r in range(n_sessions)]
        if self.kv_mode:
            self.handles = [sysm.kv.session(s) for s in self.session_ids]
        else:
            self.handles = [sysm.svc.session(s) for s in self.session_ids]
        self.payload_bytes = int(mix.get("payload_bytes", 56))
        self.pad = np.random.default_rng(seed ^ 0xFADE).bytes(1 << 16)
        self.half_ring = sysm.n_instances // 2
        # history, indexed by op id
        self.kind: list[int] = []
        self.target: list[int] = []       # key id (KV) or session rank
        self.session: list[int] = []
        self.issue_seq: list[int] = []
        self.ack_seq: list[int] = []
        self.t_issue: list[float] = []
        self.t_ack: list[float] = []
        self.ack_loglen: list[int] = []
        self.answer: dict[int, int | None] = {}
        self.value: dict[int, bytes] = {}   # put values / submit payloads
        self.pending: dict[int, int] = {}   # op id -> group
        self.seq = 0
        self.seals: dict[int, list[tuple[int, int]]] = {}
        self.snapshot_s = 0.0
        self.stalls = new_stalls()
        self.compiles = 0
        self.compiled: list[str] = []       # each program compiled or loaded
        self.t_window = 0.0                 # window start, for stall times
        self.on_ack = None
        self.due_times: dict[int, float] = {}

    # -- ops ---------------------------------------------------------------
    def _payload(self, op: int, rank: int) -> bytes:
        head = op.to_bytes(8, "little") + rank.to_bytes(4, "little")
        off = (op * 61) % ((1 << 16) - 64)
        return head + self.pad[off:off + self.payload_bytes - 12]

    def issue(self, kind: int, key: int, session: int) -> int:
        """Issue one op now (``generator.KIND_*``); returns its op id."""
        op = len(self.kind)
        self.kind.append(kind)
        self.target.append(session if kind == KIND_SUBMIT else key)
        self.session.append(session)
        self.ack_seq.append(-1)
        self.t_ack.append(-1.0)
        self.ack_loglen.append(-1)
        if kind == KIND_PUT:
            value = op.to_bytes(8, "little") + self.pad[(op * 29) % 65000:][:16]
        elif kind == KIND_SUBMIT:
            value = self._payload(op, session)
        self.issue_seq.append(self.seq)
        self.seq += 1
        self.t_issue.append(time.perf_counter())
        handle = self.handles[session]
        if kind == KIND_GET:
            got = handle.get(self.keys[key])
            self.answer[op] = None if got is None else int.from_bytes(got[:8], "little")
            self._ack(op, -1)
            self.collect()
            return op
        self.value[op] = value
        if kind == KIND_PUT:
            ticket = handle.put(self.keys[key], value)
        else:
            ticket = handle.submit(value)
        self.pending[op] = ticket.group
        if self.control:
            # the control acknowledges at submission, before the op is
            # decided: the early acknowledgement a faster client is
            # tempted by, which breaks "an acknowledged write reads back"
            self._ack(op, self.sys.log_len(ticket.group))
        return op

    def _ack(self, op: int, loglen: int) -> None:
        self.ack_seq[op] = self.seq
        self.seq += 1
        self.t_ack[op] = time.perf_counter()
        self.ack_loglen[op] = loglen
        if self.on_ack is not None:
            self.on_ack(op)

    def _op_of(self, payload: bytes) -> int | None:
        if self.kv_mode:
            # put frame: 18-byte header, 8-byte key, then the value, whose
            # first 8 bytes are the op id; read-index markers carry none
            if len(payload) < 34 or payload[2] != ref.OP_PUT:
                return None
            return int.from_bytes(payload[26:34], "little")
        return int.from_bytes(payload[:8], "little")

    def collect(self) -> None:
        """Acknowledge what the program delivered since the last call."""
        buf = self.sys.delivered
        if not buf:
            return
        if self.kv_mode:
            with self.spans.region("bench.apply"):
                self.sys.kv.refresh()
        lens: dict[int, int] = {}
        pending = self.pending
        for payload in buf:
            op = self._op_of(payload)
            if op is None or op not in pending:
                continue
            g = pending.pop(op)
            n = lens.get(g)
            if n is None:
                n = lens[g] = self.sys.log_len(g)
            if self.ack_seq[op] < 0:
                self._ack(op, n)
        buf.clear()
        self.compact(lens)

    def pump(self) -> None:
        t0 = time.perf_counter()
        with self.spans.region("bench.pump"):
            self.sys.svc.pump()
        dt = time.perf_counter() - t0
        if dt > self.stalls["pump_max_s"]:
            self.stalls["pump_max_s"] = dt
        if dt > STALL_S and len(self.stalls["long_pumps"]) < 20:
            self.stalls["long_pumps"].append((round(t0 - self.t_window, 3), round(dt, 3)))
        self.collect()

    def compact(self, groups) -> None:
        """Snapshot every group whose undrained span reached half the ring,
        half a ring at a time, so drained prefixes are whole half-rings."""
        sysm = self.sys
        if sysm.ctx.snapshots is None:
            return
        marks = sysm.seq_marks()
        for g in groups:
            while marks[g] - sysm.watermark(g) >= self.half_ring:
                t0 = time.perf_counter()
                with self.spans.region("bench.compact"):
                    snap = sysm.ctx.snapshot_group(g, upto=sysm.watermark(g) + self.half_ring)
                dt = time.perf_counter() - t0
                self.snapshot_s += dt
                self.stalls["snapshots"] += 1
                self.stalls["snapshot_max_s"] = max(self.stalls["snapshot_max_s"], dt)
                self.seals.setdefault(g, []).append((snap.watermark, int(snap.seal)))

    def drain(self, timeout_s: float) -> None:
        """Pump until every issued op is acknowledged, or ``timeout_s``."""
        end = time.perf_counter() + timeout_s
        while self.pending and time.perf_counter() < end:
            self.pump()

    # -- loops -------------------------------------------------------------
    def run_open(self, sched, t0: float, close: float) -> tuple[int, int]:
        """Issue ``sched``'s ops at ``t0 + due``, pumping whenever ops are
        outstanding, until all are issued and the clock passes ``close``.
        Returns the op id range."""
        first = len(self.kind)
        due = (sched.due + t0).tolist()
        kind, key, sess = sched.kind.tolist(), sched.key.tolist(), sched.session.tolist()
        n, i = len(due), 0
        clock = time.perf_counter
        while True:
            now = clock()
            while i < n and due[i] <= now:
                op = self.issue(kind[i], key[i], sess[i])
                self.due_times[op] = due[i]
                i += 1
            if self.pending:
                self.pump()
                continue
            if i >= n and now >= close:
                break
            nxt = due[i] if i < n else close
            wait = nxt - clock()
            if wait > 0:
                with self.spans.region("bench.wait"):
                    if wait > 0.0005:
                        time.sleep(wait - 0.0003)
                    while clock() < nxt:
                        pass
        return first, len(self.kind)

    def run_closed(self, sched, close: float) -> tuple[int, int]:
        """Keep ``sched.population`` clients busy until ``close``: each has
        one op outstanding on its own session and issues the next as soon
        as the last is acknowledged."""
        first = len(self.kind)
        kind, key, sess = sched.kind.tolist(), sched.key.tolist(), sched.session.tolist()
        slot_of: dict[int, int] = {}
        done: list[int] = []
        state = {"open": True}

        def finished(op: int) -> None:
            slot = slot_of.pop(op, None)
            if slot is not None and state["open"]:
                done.append(slot)

        def issue(slot: int) -> None:
            slot_of[len(self.kind)] = slot     # the op id issue() assigns
            self.issue(kind[slot], key[slot], sess[slot])

        self.on_ack = finished
        for slot in range(sched.population):
            issue(slot)
        clock = time.perf_counter
        while clock() < close:
            if done:
                ready = done[:]
                done.clear()
                for slot in ready:
                    issue(slot)
            if self.pending:
                self.pump()
        state["open"] = False
        self.on_ack = None
        return first, len(self.kind)


def new_stalls() -> dict:
    """The longest single host stalls of a window, by cause: a pump, a
    snapshot, a full garbage collection; and when each of the first pumps
    longer than ``STALL_S`` began, from the window's start, and how long it
    took."""
    return {"pump_max_s": 0.0, "snapshots": 0, "snapshot_max_s": 0.0,
            "gc_full": 0, "gc_full_max_s": 0.0, "long_pumps": []}


class _GcWatch:
    """``gc.callbacks`` hook timing the interpreter's full collections."""

    def __init__(self, stalls: dict):
        self.stalls, self.t0 = stalls, 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self.t0 = time.perf_counter()
        else:
            dt = time.perf_counter() - self.t0
            self.stalls["gc_full"] += 1
            self.stalls["gc_full_max_s"] = max(self.stalls["gc_full_max_s"], dt)


def _count_compiles(client_box: list):
    def listener(event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT and client_box:
            c = client_box[0]
            c.compiles += 1
            at = time.perf_counter() - c.t_window
            c.compiled.append(f"{kw.get('fun_name', '?')} {secs:.3f}s at {at:.3f}s")
    return listener


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def load_kv(client: Client, chunk: int = 2048) -> None:
    """Set-up: one put per key, in key order, through the sessions."""
    s = len(client.handles)
    for lo in range(0, client.n_keys, chunk):
        for k in range(lo, min(lo + chunk, client.n_keys)):
            client.issue(KIND_PUT, k, k % s)
        client.drain(600.0)


def warm_up(client: Client, cell: Cell, seed: int, max_s: float) -> int:
    """Set-up: run the cell's own mix in chunks of ``WARM_CHUNK_S``, at the
    mix's own load and then at each multiple in ``WARM_LOADS`` (a queue
    behind a stall in the window presents the larger batches that a higher
    load does), and repeat that pass until a whole pass compiles nothing,
    or ``max_s``.  The program alone decides what compiles; the harness
    only watches.  Returns the number of passes."""
    t_start = time.perf_counter()
    passes = 0
    while True:
        before = client.compiles
        for m in WARM_LOADS:
            sched = cell.generator.schedule(_scaled(cell.traffic, m),
                                            seed ^ WARM_SEED ^ (passes << 8) ^ m,
                                            WARM_CHUNK_S, client.n_keys)
            t0 = time.perf_counter()
            if sched.loop == "open":
                client.run_open(sched, t0, t0 + WARM_CHUNK_S)
            else:
                client.run_closed(sched, t0 + WARM_CHUNK_S)
            client.drain(600.0)
        passes += 1
        if client.compiles == before or time.perf_counter() - t_start >= max_s:
            return passes


def _scaled(mix: dict, m: int) -> dict:
    """The mix at ``m`` times its load: its rate, or its population."""
    out = dict(mix)
    key = "rate_per_s" if mix["loop"] == "open" else "population"
    out[key] = mix[key] * m
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, control: bool = False, drain_timeout_s: float = 60.0,
        max_warmup_s: float = 120.0, trace_dir: str | None = None,
        on_system=None) -> dict:
    """One run of ``cell``.  Returns the result line's fields, the checks,
    and the readings the per-layer metrics are read from."""
    import jax

    sysm = System(cell.config, cell.chips)
    if on_system is not None:
        on_system(sysm)
    spans = Spans() if trace else None
    client = Client(sysm, cell.config, cell.traffic, seed, control=control,
                    spans=spans)
    box = [client]
    jax.monitoring.register_event_duration_secs_listener(_count_compiles(box))
    # (burst, rounds, member groups' sequencer marks) per dispatch
    wire: list[tuple[int, int, tuple]] = []
    if trace:
        _instrument(client, spans, wire)
    if client.kv_mode:
        load_kv(client)

    warm_up(client, cell, seed, max_warmup_s)

    sched = cell.generator.schedule(cell.traffic, seed, seconds, client.n_keys)
    counts0 = sysm.dispatch_counts()
    client.compiles = 0
    client.compiled = []
    client.snapshot_s = 0.0
    client.stalls = new_stalls()
    gc_watch = _GcWatch(client.stalls)
    gc.callbacks.append(gc_watch)
    if spans is not None:
        spans.reset()
        wire.clear()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    t0 = client.t_window = time.perf_counter()
    close = t0 + seconds
    window_span = spans.region("bench.window") if spans else _NULL
    with window_span:
        if sched.loop == "open":
            first, last = client.run_open(sched, t0, close)
        else:
            first, last = client.run_closed(sched, close)
    t_close = time.perf_counter()
    gc.callbacks.remove(gc_watch)
    compiles = client.compiles
    counts1 = sysm.dispatch_counts()
    if spans is not None:
        jax.profiler.stop_trace()
    client.drain(drain_timeout_s)
    devices = jax.devices()[:cell.chips]
    peak = _peak_bytes(devices)

    checks = check(client, sysm)
    window = range(first, last)
    acked_t = np.array([client.t_ack[o] for o in window])
    in_window = int(np.sum((acked_t >= t0) & (acked_t <= t_close)))
    failed = sum(1 for o in window if client.t_ack[o] < 0)
    metrics = {
        "ops_per_s": in_window / (t_close - t0),
        "setup_s": setup_s,
    }
    readings = {
        "loop": sched.loop,
        "window_s": t_close - t0,
        "acked": in_window,
        "dispatches": {k: counts1[k] - counts0[k] for k in counts0},
        "compiles": compiles,
        "compiled": client.compiled[:compiles],
        "spans": spans.stats if spans is not None else {},
        "snapshot_s": client.snapshot_s,
        "stalls": dict(client.stalls),
        "wire_dispatches": wire,
        "config": cell.config,
    }
    if sched.loop == "open":
        lat, late = open_latency(client, window)
        metrics["op_p50_ms"] = float(np.percentile(lat, 50) * 1e3) if lat.size else float("nan")
        metrics["op_p99_ms"] = float(np.percentile(lat, 99) * 1e3) if lat.size else float("nan")
        readings["lateness_s"] = late
        readings["latency_s"] = lat
    else:
        readings["closed_latency_s"] = np.array([
            client.t_ack[o] - client.t_issue[o]
            for o in window if t0 <= client.t_ack[o] <= t_close])
    checks["unacked"] = failed
    d = devices[0]
    return {
        "attempted": last - first,
        "failed": failed,
        "metrics": metrics,
        "checks": checks,
        "readings": readings,
        "device": {
            "platform": d.platform,
            "kind": d.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak,
        },
    }


def open_latency(client: Client, ops) -> tuple[np.ndarray, np.ndarray]:
    """Open-loop latency of each acknowledged op, from its due time -- so a
    stall also counts against the ops that fell due while it lasted -- and
    how late each op was issued."""
    lat = np.array([client.t_ack[o] - client.due_times[o]
                    for o in ops if client.t_ack[o] >= 0])
    late = np.array([client.t_issue[o] - client.due_times[o] for o in ops])
    return lat, late


def _peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _instrument(client: Client, spans: Spans, wire: list) -> None:
    """Traced runs: host spans around the calls into each layer, and a
    record of each wire-path dispatch's shape for the roofline."""
    sysm = client.sys
    if client.kv_mode:
        for h in client.handles:
            spans.wrap(h, "put", "kv.put")
            spans.wrap(h, "get", "kv.get")
        spans.wrap(sysm.kv, "refresh", "kv.refresh")
    spans.wrap(sysm.ctx, "submit", "ctx.submit")
    spans.wrap(sysm.svc, "pump", "svc.pump")
    hw = sysm.hw
    marks = sysm.seq_marks
    if sysm.ctx.grouped:
        def cohort(args):
            m = marks()
            wire.append((args[1].shape[1], 1, tuple(m[g] for g in args[0])))

        def persistent(args):
            m = marks()
            wire.append((args[1].shape[2], args[1].shape[0],
                         tuple(m[g] for g in args[0])))

        spans.wrap(hw, "pipeline_cohort", "hw.cohort", on_call=cohort)
        spans.wrap(hw, "pipeline_persistent", "hw.persistent", on_call=persistent)
    else:
        def single(args):
            wire.append((args[0].shape[0], 1, (marks()[0],)))

        spans.wrap(hw, "pipeline", "hw.pipeline", on_call=single)


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------
def check(client: Client, sysm: System) -> dict:
    """Every number compared, each with limit 0 (see ``reference``)."""
    ctx = sysm.ctx
    g_n = sysm.n_groups
    logs = [ctx.full_group_log(g) for g in range(g_n)]
    n_ops = len(client.kind)
    group_of = [ref.group_of(s, g_n) for s in client.session_ids]
    # where each op sits in its group's log
    log_pos = [-1] * n_ops
    for log in logs:
        for pos, (_inst, payload) in enumerate(log):
            op = client._op_of(payload)
            if op is not None and 0 <= op < n_ops and log_pos[op] < 0:
                log_pos[op] = pos
    writes = [o for o in range(n_ops) if client.kind[o] != KIND_GET]
    out = {}
    out["acked_not_in_log"] = ref.check_acked_logged(
        [client.ack_loglen[o] if client.t_ack[o] >= 0 else -1 for o in writes],
        [log_pos[o] for o in writes],
    )
    if client.kv_mode:
        tags = {ref.fnv1a32(s.encode()) for s in client.session_ids}
        want = [(tags_of(client, o), client.keys[client.target[o]], client.value[o])
                for o in writes]
        out["log_mismatch"] = ref.check_kv_log(logs[0], want, tags)
        puts_by_key: dict[int, list] = {}
        for o in writes:
            puts_by_key.setdefault(client.target[o], []).append(
                (o, client.issue_seq[o], client.ack_seq[o]
                 if client.ack_seq[o] >= 0 else 1 << 62))
        gets = [(client.target[o], client.answer[o], client.issue_seq[o],
                 client.ack_seq[o]) for o in range(n_ops) if client.kind[o] == KIND_GET]
        out["stale_reads"] = ref.check_kv_reads(puts_by_key, gets)
        sysm.kv.refresh()
        state = dict(sysm.kv.replica(0).state)
        out["state_mismatch"] = ref.check_kv_state(
            state, [(client.keys[client.target[o]], client.value[o]) for o in writes])
    else:
        want = [[] for _ in range(g_n)]
        for o in writes:
            want[group_of[client.session[o]]].append(client.value[o])
        out["log_mismatch"] = ref.check_submit_logs(logs, want)
    bad = 0
    for g, seals in client.seals.items():
        insts, values = ctx.snapshots.entries(g)
        bad += ref.check_snapshots(np.asarray(insts), np.asarray(values),
                                   logs[g], seals)
    out["seal_mismatch"] = bad
    return out


def tags_of(client: Client, op: int) -> int:
    return ref.fnv1a32(client.session_ids[client.session[op]].encode())
