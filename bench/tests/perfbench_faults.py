"""Faults planted under the timed path, for the tests of ``correct``.

Each ``plant_*`` takes the harness's ``System`` and breaks the wire path's
dispatch underneath the program (ungrouped ``pipeline``, or the grouped
``pipeline_cohort``/``pipeline_persistent``), as a faulty kernel or
dataplane change would."""
from __future__ import annotations

import numpy as np


def _dispatches(sysm) -> list[str]:
    if sysm.ctx.grouped:
        return ["pipeline_cohort", "pipeline_persistent"]
    return ["pipeline"]


def _on_results(sysm, edit) -> None:
    """Apply ``edit(fresh, value)`` to each dispatch's host results."""
    hw = sysm.hw
    for name in _dispatches(sysm):
        orig = getattr(hw, name)

        def step(*args, _orig=orig, **kwargs):
            out = _orig(*args, **kwargs)
            if isinstance(out, tuple):
                fresh, inst, value = (np.array(x) for x in out)
                edit(fresh, value)
                return fresh, inst, value
            resolve = out.resolve

            def resolved():
                fresh, inst, value = (np.array(x) for x in resolve())
                edit(fresh, value)
                return fresh, inst, value

            out.resolve = resolved
            return out

        setattr(hw, name, step)


def plant_state_unchanged(sysm) -> None:
    """Every dispatch leaves the sequencer, acceptor and learner state as
    it found it, so the next one reuses the same instances."""
    import jax
    import jax.numpy as jnp

    hw = sysm.hw
    for name in _dispatches(sysm):
        orig = getattr(hw, name)

        def step(*args, _orig=orig, **kwargs):
            saved = jax.tree_util.tree_map(jnp.copy, (hw.cstate, hw.stack, hw.lstate))
            marks = list(hw._seq_marks())
            out = _orig(*args, **kwargs)
            hw.cstate, hw.stack, hw.lstate = saved
            if sysm.ctx.grouped:
                hw.next_inst_host[:] = marks
            else:
                hw._next_inst_host = marks[0]
            return out

        setattr(hw, name, step)


def plant_half_batch(sysm) -> None:
    """Each dispatch reports only the first half of its lanes."""
    def edit(fresh, _value):
        fresh[..., fresh.shape[-1] // 2:] = False

    _on_results(sysm, edit)


def plant_altered_answer(sysm) -> None:
    """The first delivered lane of each dispatch carries one flipped bit
    inside its payload."""
    def edit(fresh, value):
        flat_f = fresh.reshape(-1)
        flat_v = value.reshape(-1, value.shape[-1])
        hits = np.nonzero(flat_f)[0]
        if hits.size:
            flat_v[hits[0], 13] ^= 1

    _on_results(sysm, edit)


FAULTS = {
    "state_unchanged": plant_state_unchanged,
    "half_batch": plant_half_batch,
    "altered_answer": plant_altered_answer,
}
