"""Named host spans on the profiler's clock, and a tally of their time.

Every span the engine opens is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``; its keyword arguments become the event's stats in a
trace captured with ``jax.profiler.trace``, on the same clock as the
device planes.  Each span also adds its host seconds and one call to a
process-wide tally by name (:func:`span_totals`), with or without a
profiler, from any thread.  A span costs about a microsecond either way,
so it needs no switch.
"""
from __future__ import annotations

import functools
import threading
import time

import jax

PREFIX = "repro."

_LOCK = threading.Lock()
_TOTALS: "dict[str, list]" = {}   # name -> [seconds, calls]


class _Span:
    """The annotation plus a host clock; ``with`` yields the annotation,
    so ``set_metadata`` adds args known only inside the span."""

    __slots__ = ("name", "annotation", "t0")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.annotation = jax.profiler.TraceAnnotation(PREFIX + name, **args)

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self.annotation.__enter__()

    def __exit__(self, *exc):
        out = self.annotation.__exit__(*exc)
        dt = time.perf_counter() - self.t0
        with _LOCK:
            total = _TOTALS.get(self.name)
            if total is None:
                _TOTALS[self.name] = [dt, 1]
            else:
                total[0] += dt
                total[1] += 1
        return out


def span(name: str, **args) -> _Span:
    """``with span("chunk", K=128, start=0, end=8192): ...``"""
    return _Span(name, args)


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def span_totals() -> "dict[str, tuple[float, int]]":
    """``{name: (host seconds, calls)}`` of every span closed so far in
    this process, names without the ``repro.`` prefix.  Nested spans
    count in full, each under its own name; a caller reads a window as
    the difference of two readings."""
    with _LOCK:
        return {name: (s, c) for name, (s, c) in _TOTALS.items()}
