"""The single-group fused dispatch's host boundary (``HardwareDataplane.pipeline``).

A steady-state dispatch makes exactly one host->device transfer (the burst)
and one device->host transfer (the packed result); everything else the
program reads stays on the device, and ``quorum`` is compiled in.  The packed
result splits back into the very ``(fresh, inst, value)`` the engine computes,
dispatch for dispatch, on both engines (the kernel under interpret here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched
from repro.core import plan as plan_mod
from repro.core.api import HardwareDataplane
from repro.core.snapshot import RingOverflowError
from repro.core.types import PaxosConfig
from repro.kernels import ops as kops

ENGINES = [pytest.param(False, id="jnp"), pytest.param(True, id="kernel")]
# the engines as the dispatch jitted them before its result was packed
_JNP_ROUND = jax.jit(batched.fused_round)
_KERNEL_ROUND = jax.jit(kops.fused_round, static_argnames=("window_blocks",))


def _burst(rng, b, v):
    vals = rng.integers(-(2**31), 2**31 - 1, size=(b, v), dtype=np.int32)
    active = rng.random(b) < 0.8
    return vals, active


class _Counted:
    """Counts the calls of one transfer function and runs each with explicit
    transfers allowed, while the test's guard refuses every other one."""

    def __init__(self, fn, guard):
        self.fn, self.guard, self.calls = fn, guard, 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        with self.guard("allow"):
            return self.fn(*args, **kwargs)


@pytest.mark.parametrize("use_kernels", ENGINES)
@pytest.mark.parametrize("reclaim", [False, True], ids=["plain", "reclaim"])
def test_steady_state_dispatch_transfers_once_each_way(monkeypatch, use_kernels, reclaim):
    cfg = PaxosConfig(n_acceptors=3, n_instances=1024, batch=32)
    hw = HardwareDataplane(cfg, use_kernels=use_kernels)
    if reclaim:
        hw.enable_reclamation()
    rng = np.random.default_rng(0)
    bursts = [_burst(rng, b, cfg.value_words) for b in (8, 32, 8, 16, 8, 32)]
    for vals, active in bursts:  # warm every shape: compiles are not steady state
        hw.pipeline(vals, active)
    put = _Counted(jax.device_put, jax.transfer_guard_host_to_device)
    get = _Counted(jax.device_get, jax.transfer_guard_device_to_host)
    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(jax, "device_get", get)
    for n, (vals, active) in enumerate(bursts, start=1):
        # explicit transfers are refused too: only the counted ones may pass
        with jax.transfer_guard("disallow_explicit"):
            fresh, inst, value = hw.pipeline(vals, active)
        assert (put.calls, get.calls) == (n, n)
        assert value.shape == vals.shape and len(fresh) == len(inst) == len(vals)


@pytest.mark.parametrize("use_kernels", ENGINES)
def test_quorum_is_compiled_in(monkeypatch, use_kernels):
    cfg = PaxosConfig(n_acceptors=3, n_instances=512, batch=16)
    hw = HardwareDataplane(cfg, use_kernels=use_kernels)
    hw.enable_reclamation()
    seen = []
    jitted = hw._fused

    def spy(*args, **kwargs):
        seen.append((args, kwargs))
        return jitted(*args, **kwargs)

    monkeypatch.setattr(hw, "_fused", spy)
    vals, active = _burst(np.random.default_rng(1), 16, cfg.value_words)
    hw.pipeline(vals, active)
    [(args, kwargs)] = seen
    assert kwargs["quorum"] == cfg.quorum
    # every positional operand is already a device array (or absent)
    assert all(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(args))
    _args_info, kwargs_info = jitted.lower(*args, **kwargs).args_info
    assert "quorum" not in kwargs_info  # static: no traced argument


class _Unpacked:
    """The engine called as the dispatch called it before packing: the burst,
    the active mask, a Python-int quorum and the limit as separate arguments,
    and the three results read back one by one."""

    def __init__(self, cfg, use_kernels):
        self.hw = HardwareDataplane(cfg, use_kernels=use_kernels)

    def pipeline(self, values, active):
        hw = self.hw
        b = values.shape[0]
        hw._guard_capacity(hw._next_inst_host, b)
        nblk = (
            plan_mod.window_blocks(hw.cfg.n_instances, [hw._next_inst_host], b)
            if hw.use_kernels
            else None
        )
        args = [
            hw.cstate,
            hw.stack,
            hw.lstate,
            jnp.asarray(values),
            jnp.asarray(active),
            hw.alive_mask,
            hw.cfg.quorum,
        ]
        if hw.reclaimed_host is not None:
            args.append(jnp.int32(hw.reclaimed_host + hw.cfg.n_instances))
        if nblk is None:
            out = _JNP_ROUND(*args)
        else:
            out = _KERNEL_ROUND(*args, window_blocks=nblk)
        hw.cstate, hw.stack, hw.lstate, fresh, inst, _win, value = out
        hw._next_inst_host += b
        return np.asarray(fresh), np.asarray(inst), np.asarray(value)


def _same(got, want):
    for g, w in zip(got, want, strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


# Each case is a ring size, whether reclamation is on, and its steps: "b<n>"
# a burst of n lanes, "o<n>" a burst the door must refuse on both sides,
# "r<n>" set_reclaimed(n), "k<a>"/"v<a>" kill/revive acceptor a.
CASES = {
    # every quantized burst below the 128-slot ring block
    "sub128": (1024, False, "b8 b16 b32 b64 b8 b8 b16"),
    # windows that start in one ring block and end in the next
    "straddle": (1024, False, "b8 " * 15 + "b16 b128 b64"),
    # more instances than the ring holds: slots are overwritten on wrap
    "wrap": (256, False, "b64 b32 b128 b8 b128 b64 b32 b128 b16"),
    # a one-block ring: a 128 window off its block start runs the jnp engine
    # even on a kernel dataplane
    "one_block_ring": (128, False, "b8 b128 b16 b128 b64"),
    # the limit moves between dispatches, up to the door's refusal
    "reclaim": (16, True, "b8 b8 o8 r8 b8 o8 r24 b8 b8 o8"),
    # one acceptor down keeps the quorum; two lose it; revival restores it
    "killed": (256, False, "b16 k1 b16 b8 k0 b8 v1 b16"),
}


@pytest.mark.parametrize("use_kernels", ENGINES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_packed_pipeline_equals_engine_call(case, use_kernels):
    n_instances, reclaim, steps = CASES[case]
    cfg = PaxosConfig(n_acceptors=3, n_instances=n_instances, batch=128)
    hw = HardwareDataplane(cfg, use_kernels=use_kernels)
    ref = _Unpacked(cfg, use_kernels)
    sides = (hw, ref.hw)
    if reclaim:
        for side in sides:
            side.enable_reclamation()
    rng = np.random.default_rng(sum(map(ord, case)))
    fresh_seen = []
    for step in steps.split():
        op, arg = step[0], int(step[1:])
        if op == "b":
            vals, active = _burst(rng, arg, cfg.value_words)
            got = hw.pipeline(vals, active)
            _same(got, ref.pipeline(vals, active))
            fresh_seen.append(bool(got[0].any()))
        elif op == "o":
            vals, active = _burst(rng, arg, cfg.value_words)
            for call in (hw.pipeline, ref.pipeline):
                with pytest.raises(RingOverflowError):
                    call(vals, active)
        elif op == "r":
            for side in sides:
                side.set_reclaimed(arg)
        elif op == "k":
            for side in sides:
                side.kill_acceptor(arg)
        else:
            for side in sides:
                side.revive_acceptor(arg)
    if case == "killed":
        # the quorum was lost exactly while two of three acceptors were down
        assert fresh_seen == [True, True, True, False, True]
    state = jax.tree_util.tree_leaves((hw.stack, hw.lstate, hw.cstate))
    ref_state = jax.tree_util.tree_leaves((ref.hw.stack, ref.hw.lstate, ref.hw.cstate))
    for x, y in zip(state, ref_state, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
