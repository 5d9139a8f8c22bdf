"""Program spans: named host steps in the profiler's own trace.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler session is
running, it writes one event to the host plane of the trace, on the same
timeline as the device's programs; otherwise it records nothing and costs
about a microsecond.  There is no other sink and no switch: starting a
profiler session (``jax.profiler.start_trace``) is what turns spans on.

Names are ``repro.<layer>.<step>``.  A span goes around one step of the
host path (a pump, a wire burst, a launch, a read-back), never around the
body of a per-op loop.  Metadata known when the step starts rides on the
span's constructor; metadata known only at its end is attached with
``set_metadata`` under ``enabled()``, so the off state pays for no more
than reading an int::

    with obs.span("repro.ctx.deliver") as sp:
        n = deliver_all()
        if obs.enabled():
            sp.set_metadata(delivered=n)
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    """The step ``name``, with ``meta`` as the event's metadata."""
    return TraceAnnotation(name, **meta)


def enabled() -> bool:
    """True while a profiler session records spans: the gate for metadata
    that costs more to compute than reading an int."""
    return TraceAnnotation.is_enabled()
