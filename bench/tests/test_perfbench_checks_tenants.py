"""``correct`` on the tenant cell's path, on the CPU at a small size: a sound
run is correct; the control (submits acknowledged at submission) and each
fault planted under the wire path come out incorrect."""
from __future__ import annotations

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import harness  # noqa: E402
from bench.traffic import generator  # noqa: E402
from perfbench_faults import FAULTS  # noqa: E402

CONFIG = {
    "front_end": "service",
    "paxos": {"n_acceptors": 3, "n_instances": 1024, "value_words": 16,
              "batch": 32, "n_groups": 8},
    "context": {"snapshots": True},
}
MIX = {"generator": "generator", "loop": "closed", "population": 48,
       "ops": {"submit": 1.0}, "sessions": {"count": 256, "dist": "zipfian", "theta": 0.99},
       "payload_bytes": 56}
SEED = 2**31 + 77


def run(control=False, fault=None):
    cell = harness.Cell("tenants.small", 1, CONFIG, MIX, generator, [], [])
    return harness.run(cell, SEED, 2.0, False, time.perf_counter(),
                       control=control, drain_timeout_s=3.0, max_warmup_s=30.0,
                       on_system=fault)


@pytest.fixture(scope="module")
def sound():
    return run()


def test_sound_run_is_correct(sound):
    assert sound["checks"] == {
        "acked_not_in_log": 0, "log_mismatch": 0, "seal_mismatch": 0, "unacked": 0}
    assert sound["failed"] == 0 and sound["attempted"] > 1000
    # the small ring wraps several times, so seals were checked
    assert sound["readings"]["snapshot_s"] > 0


def test_control_is_incorrect(sound):
    checks = run(control=True)["checks"]
    assert checks["acked_not_in_log"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_incorrect(sound, fault):
    checks = run(fault=FAULTS[fault])["checks"]
    assert any(checks.values()), checks
