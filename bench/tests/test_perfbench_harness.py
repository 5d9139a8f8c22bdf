"""The harness without a chip: schedules from seeds, files found by name,
latency from due time, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.traffic import generator  # noqa: E402

KV_MIX = {"generator": "generator", "loop": "open", "rate_per_s": 2000,
          "ops": {"get": 0.5, "put": 0.5}, "keys": {"dist": "zipfian", "theta": 0.99},
          "sessions": {"count": 16, "dist": "uniform"}}
CLOSED_MIX = {"generator": "generator", "loop": "closed", "population": 64,
              "ops": {"submit": 1.0},
              "sessions": {"count": 4096, "dist": "zipfian", "theta": 0.99}}


def test_same_seed_same_schedule():
    big = 2**31 + 987654321
    for mix in (KV_MIX, CLOSED_MIX):
        a = generator.schedule(mix, big, 3.0, 20000)
        b = generator.schedule(mix, big, 3.0, 20000)
        for field in ("kind", "key", "session"):
            assert np.array_equal(getattr(a, field), getattr(b, field))
        assert (a.due is None and b.due is None) or np.array_equal(a.due, b.due)
        c = generator.schedule(mix, big + 1, 3.0, 20000)
        assert not np.array_equal(a.session, c.session)


def test_seeds_offer_the_same_work_in_another_order():
    a = generator.schedule(KV_MIX, 1, 3.0, 20000)
    b = generator.schedule(KV_MIX, 2, 3.0, 20000)
    assert len(a) == len(b) == 6000
    assert np.allclose(np.sort(np.diff(a.due, prepend=0.0)),
                       np.sort(np.diff(b.due, prepend=0.0)))
    assert a.due[-1] < 3.0 and b.due[-1] < 3.0
    assert np.array_equal(np.bincount(a.kind), np.bincount(b.kind))
    assert np.array_equal(np.sort(np.bincount(a.key, minlength=20000)),
                          np.sort(np.bincount(b.key, minlength=20000)))
    assert np.array_equal(np.bincount(a.session), np.bincount(b.session))
    assert abs(np.bincount(a.kind)[generator.KIND_GET] - 3000) <= 1


def test_zipf_ranks_follow_the_weights():
    ranks = generator.stratified_zipf(4096, 0.99, 1 << 16)
    counts = np.bincount(ranks, minlength=4096)
    w = 1.0 / np.arange(1, 4097) ** 0.99
    assert abs(counts[0] / (1 << 16) - w[0] / w.sum()) < 1e-3
    assert np.all(np.diff(counts[:64]) <= 0)


def _write(path: str, text: str) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_new_config_traffic_and_metric_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    bm = {
        "end_to_end": [{"name": "ops_per_s", "unit": "ops/s"},
                       {"name": "op_p99_ms", "unit": "ms", "workloads": ["other"]}],
        "per_layer": [
            {"name": "fresh_metric.tput", "unit": "count", "moves": "ops_per_s"},
            {"name": "lat_only", "unit": "ms", "moves": "op_p99_ms"},
            {"name": "listed", "unit": "ms", "moves": "op_p99_ms",
             "workloads": ["newcfg.newmix"]},
        ],
        "workloads": [{"name": "newcfg.newmix", "config": "newcfg",
                       "traffic": "newmix", "chips": 1}],
    }
    _write(str(tmp_path / "BENCHMARK.json"), json.dumps(bm))
    _write(str(bench / "configs" / "newcfg.json"), json.dumps({"paxos": {"n_groups": 3}}))
    _write(str(bench / "traffic" / "newmix.json"),
           json.dumps({"generator": "newgen", "loop": "closed"}))
    _write(str(bench / "traffic" / "newgen.py"), "def schedule(*a):\n    return 'new'\n")
    _write(str(bench / "metrics" / "fresh_metric.py"), "def read(r):\n    return 7.0\n")
    cell = harness.load_cell("newcfg.newmix", str(bench))
    assert cell.config == {"paxos": {"n_groups": 3}}
    assert cell.traffic["loop"] == "closed"
    assert cell.generator.schedule() == "new"
    assert [m["name"] for m in cell.end_to_end] == ["ops_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["fresh_metric.tput", "listed"]
    assert harness.metric_reader("fresh_metric.tput", str(bench))({}) == 7.0
    assert harness.metric_reader("fresh_metric", str(bench))({}) == 7.0


def test_benchmark_cells_resolve():
    bm = harness.benchmark()
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))


# ---------------------------------------------------------------------------
# latency from due time, on a synthetic stall
# ---------------------------------------------------------------------------
class _FakeSvc:
    """Delivers everything submitted at each pump; one pump stalls."""

    def __init__(self, sysm, stall_after: float, stall_s: float):
        self.sysm, self.inbox = sysm, []
        self.stall_at, self.stall_s = stall_after, stall_s

    def session(self, sid):
        svc = self

        class _S:
            def submit(self, payload):
                svc.inbox.append(payload)
                return types.SimpleNamespace(group=0)

        return _S()

    def pump(self):
        if self.stall_at is not None and time.perf_counter() >= self.stall_at:
            self.stall_at = None
            time.sleep(self.stall_s)
        self.sysm.delivered.extend(self.inbox)
        self.sysm.n_logged += len(self.inbox)
        self.inbox.clear()


class _FakeSystem:
    kv = None
    n_instances = 1024
    ctx = types.SimpleNamespace(snapshots=None)

    def __init__(self, stall_after, stall_s):
        self.delivered, self.n_logged = [], 0
        self.svc = _FakeSvc(self, stall_after, stall_s)

    def log_len(self, _g):
        return self.n_logged


def test_latency_counts_from_due_time_across_a_stall():
    mix = {"loop": "open", "rate_per_s": 1000, "ops": {"submit": 1.0},
           "sessions": {"count": 4, "dist": "uniform"}}
    sched = generator.schedule(mix, 5, 1.0)
    t0 = time.perf_counter() + 0.05
    stall_from, stall_s = t0 + 0.3, 0.2
    fake = _FakeSystem(stall_from, stall_s)
    client = harness.Client(fake, {}, mix, 5)
    first, last = client.run_open(sched, t0, t0 + 1.0)
    assert last - first == len(sched) and not client.pending
    ops = range(first, last)
    lat, late = harness.open_latency(client, ops)
    due = np.array([client.due_times[o] for o in ops])
    stall_end = stall_from + stall_s
    hit = (due > stall_from + 0.03) & (due < stall_end - 0.01)
    assert hit.sum() > 100
    # ops that fell due during the stall wait out its rest, though each
    # was issued and acknowledged within a pump once the stall ended
    assert np.all(lat[hit] >= stall_end - due[hit] - 1e-3)
    assert np.all(late[hit] >= stall_end - due[hit] - 1e-3)
    served = np.array([client.t_ack[o] - client.t_issue[o] for o in ops])
    assert np.median(served[hit]) < 0.05
    assert np.median(lat[~hit]) < 0.05
    assert np.percentile(lat, 99) > 0.1


# ---------------------------------------------------------------------------
# no chip, no result
# ---------------------------------------------------------------------------
def _run_py(cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kv_a3.ycsb_a.r80",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    out = _run_py(ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip()


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(str(tmp_path))
    assert out.returncode != 0
    assert not out.stdout.strip()
