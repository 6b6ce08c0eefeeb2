"""Named host spans on the profiler's clock.

Every span the engine opens is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``; its keyword arguments become the event's stats in a
trace captured with ``jax.profiler.trace``, on the same clock as the
device planes.  With no profiler capturing, a span costs about a
microsecond, so it needs no switch.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """``with span("chunk", K=128, start=0, end=8192): ...``"""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
