"""``correct`` on the four-chip tenants cell's path, on the CPU at a small
size: 16 groups of a 1,024-instance ring over four virtual devices, in a
process of its own.  A sound run is correct; the control (submits
acknowledged at submission) and each fault planted under the wire path
come out incorrect; and the cell finds its configuration, its mix and a
reader for every per-layer metric."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import harness  # noqa: E402
from perfbench_faults import FAULTS  # noqa: E402

CELL = "tenants1024x4.zipf.r80"

RUN = """
    import dataclasses, json, sys, time
    sys.path.insert(0, "bench/tests")
    import jax
    from bench import harness
    from perfbench_faults import FAULTS

    assert len(jax.devices()) == 4
    cell = harness.load_cell(%r)
    config = dict(cell.config, paxos=dict(cell.config["paxos"], n_groups=16,
                                          n_instances=1024, batch=32))
    cell = dataclasses.replace(cell, config=config,
                               traffic=dict(cell.traffic, rate_per_s=400))

    def run(control=False, fault=None):
        res = harness.run(cell, 2**31 + 91, 2.0, False, time.perf_counter(),
                          control=control, drain_timeout_s=5.0,
                          max_warmup_s=15.0, on_system=fault)
        return {"checks": res["checks"], "failed": res["failed"],
                "attempted": res["attempted"],
                "snapshot_s": res["readings"]["snapshot_s"],
                "chips": res["device"]["count"]}

    out = {"sound": run(), "control": run(control=True)}
    out["faults"] = {name: run(fault=f)["checks"] for name, f in sorted(FAULTS.items())}
    print("CHECKS " + json.dumps(out))
""" % CELL


@pytest.fixture(scope="module")
def runs():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import os\n"
         'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"\n'
         + textwrap.dedent(RUN)],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("CHECKS "))
    return json.loads(line[len("CHECKS "):])


def test_sound_run_is_correct(runs):
    sound = runs["sound"]
    assert sound["checks"] == {
        "acked_not_in_log": 0, "log_mismatch": 0, "seal_mismatch": 0, "unacked": 0}
    assert sound["failed"] == 0 and sound["attempted"] >= 800
    assert sound["chips"] == 4
    # the small rings wrap, so snapshots were taken and their seals checked
    assert sound["snapshot_s"] > 0


def test_control_is_incorrect(runs):
    assert runs["control"]["checks"]["acked_not_in_log"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_incorrect(runs, fault):
    checks = runs["faults"][fault]
    assert any(checks.values()), checks


def test_cell_finds_its_files():
    cell = harness.load_cell(CELL)
    assert cell.chips == 4 and cell.config["sharded"]
    assert cell.config["paxos"]["n_groups"] == 1024
    assert cell.traffic["loop"] == "open" and cell.traffic["payload_bytes"] == 56
    assert [m["name"] for m in cell.end_to_end] == ["ops_per_s", "setup_s"]
    assert len(cell.per_layer) == 7
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
