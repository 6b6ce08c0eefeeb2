"""The program's own spans (``repro.*``) in a profiler trace, read on the
clock of the device planes, beside what :mod:`benchlib.trace` reads.

The program opens a span around each step of a census (``repro.stage``,
``repro.schedule``, one ``repro.chunk`` per chunk dispatched with its
tile width ``K`` and bounds, ``repro.fetch``, ...).  A program without
them reads as none here: every function returns an empty or ``None``
result and raises nothing.

* :func:`load` — :func:`benchlib.trace.load`'s records, plus every host
  span of the benchmark and of the program with its arguments and
  thread, and each chip's module events with their full names (the
  compiled program's fingerprint included).
* :func:`read` — a :class:`Reading` of the window: host seconds per
  span name, device seconds per tile width ``K``, and the idle gaps
  named by the innermost span that covers each.

Device seconds per ``K`` come from pairing.  The device runs one
compiled chunk program per ``K`` (its module event carries that
program's fingerprint), in the order the host dispatched them; so the
i-th ``repro.chunk`` span pairs with the i-th chunk module event.  A
span holds no device event (those run on the runtime's threads), so
containment cannot pair them.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses

from . import trace

PROGRAM_PREFIX = "repro."
CHUNK_SPAN = "repro.chunk"
CHUNK_MODULE = "jit_pallas_chunk"
OUTSIDE = "outside any span"


@dataclasses.dataclass
class Span:
    """One host span: its name, interval, arguments (the event's stats)
    and the thread that opened it (host plane and line)."""
    name: str
    start_ns: float
    end_ns: float
    args: dict
    thread: tuple


@dataclasses.dataclass
class ProgramTrace:
    base: trace.Trace  # device operations and the benchmark's spans
    host: list         # [Span]: bench.* and repro.*
    modules: dict      # device plane -> [(start_ns, end_ns, full name)]


def load(path: str = None, *, data=None) -> ProgramTrace:
    """Read a ``.xplane.pb`` file (or a ``ProfileData``)."""
    if data is None:
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
    host, modules = [], {}
    for plane in data.planes:
        if trace._device_plane(plane.name):
            for line in plane.lines:
                if line.name == trace.MODULE_LINE:
                    modules[plane.name] = [
                        (float(e.start_ns), float(e.start_ns + e.duration_ns),
                         e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                host.extend(
                    Span(e.name.split("#")[0], float(e.start_ns),
                         float(e.start_ns + e.duration_ns), dict(e.stats),
                         (plane.name, i))
                    for e in line.events
                    if e.name.startswith((trace.SPAN_PREFIX, PROGRAM_PREFIX)))
    return ProgramTrace(base=trace.load(data=data), host=host,
                        modules=modules)


@dataclasses.dataclass
class Reading:
    span_s: dict        # span name -> (host seconds, count) in the window
    bucket_s: object    # {K: device seconds} of its chunk programs, or None
    bucket_layer_s: object  # {K: {layer: device seconds}}, or None
    idle_gaps: list     # [(innermost span, seconds)], most time first


def window_thread(pt: ProgramTrace):
    """The thread that opened the benchmark's window span, or None."""
    for s in pt.host:
        if s.name == trace.WINDOW_SPAN:
            return s.thread
    return None


def span_seconds(pt: ProgramTrace, lo: float, hi: float) -> dict:
    """``{name: (seconds, count)}`` of the host spans that start inside
    ``[lo, hi)``, each clipped to it."""
    out = collections.defaultdict(lambda: [0.0, 0])
    for s in pt.host:
        if lo <= s.start_ns < hi:
            acc = out[s.name]
            acc[0] += (min(s.end_ns, hi) - s.start_ns) * 1e-9
            acc[1] += 1
    return {k: tuple(v) for k, v in out.items()}


def buckets(pt: ProgramTrace, lo: float, hi: float, devices, layers):
    """``(seconds, layer_seconds)``: ``{K: device seconds}`` of the chunk
    programs inside ``[lo, hi)``, and ``{K: {layer: device seconds}}`` of
    their operations.  ``None`` unless one chip ran them, one thread
    dispatched them, every chunk module event pairs with a
    ``repro.chunk`` span, and each module fingerprint has one ``K``."""
    if len(devices) != 1:
        return None
    chunks = sorted((s for s in pt.host
                     if s.name == CHUNK_SPAN and lo <= s.start_ns < hi),
                    key=lambda s: s.start_ns)
    events = sorted(ev for ev in pt.modules.get(devices[0], [])
                    if ev[2].split("(")[0] == CHUNK_MODULE
                    and lo <= ev[0] < hi)
    if (not events or len(events) != len(chunks)
            or len({s.thread for s in chunks}) != 1):
        return None
    k_of: dict = {}
    seconds = collections.Counter()
    for span, (start, end, name) in zip(chunks, events):
        k = span.args.get("K")
        if k is None or k_of.setdefault(name, k) != k:
            return None
        seconds[k] += (min(end, hi) - start) * 1e-9
    starts = [ev[0] for ev in events]
    by_layer: dict = collections.defaultdict(collections.Counter)
    for op in pt.base.ops.get(devices[0], []):
        if op.module != CHUNK_MODULE:
            continue
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if i < 0 or op.start_ns >= events[i][1]:
            continue
        k = k_of[events[i][2]]
        for layer, patterns in layers.items():
            if trace.in_layer(op, patterns):
                by_layer[k][layer] += op.dur_ns * 1e-9
    return dict(seconds), {k: dict(v) for k, v in by_layer.items()}


def _innermost(pt: ProgramTrace):
    """``(lo, hi) -> name`` of the latest-starting span that holds the
    interval's midpoint: the benchmark's spans, and the program's on the
    window's thread (spans on one thread nest)."""
    thread = window_thread(pt)
    spans = sorted((s.start_ns, s.end_ns, s.name) for s in pt.host
                   if s.name != trace.WINDOW_SPAN
                   and (s.name.startswith(trace.SPAN_PREFIX)
                        or s.thread == thread))
    starts = [s for s, _, _ in spans]

    def label(lo, hi) -> str:
        mid = 0.5 * (lo + hi)
        i = bisect.bisect_right(starts, mid) - 1
        for s, e, name in reversed(spans[:i + 1]):
            if s <= mid <= e:
                return name
        return OUTSIDE
    return label


def idle_gaps(pt: ProgramTrace, lo: float, hi: float, devices,
              top: int = 10) -> list:
    """``[(span, seconds)]``: the time in ``[lo, hi)`` in which no
    operation ran on a chip, by the innermost span that covers each gap,
    averaged over ``devices``."""
    label = _innermost(pt)
    gaps = collections.Counter()
    for plane in devices:
        inside = [trace._clip(op.start_ns, op.start_ns + op.dur_ns, lo, hi)
                  for op in pt.base.ops.get(plane, [])]
        merged = trace._union([(s, e) for s, e in inside if e > s])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[label(a, b)] += b - a
    return [(k, v / len(devices) * 1e-9) for k, v in gaps.most_common(top)]


def read(pt: ProgramTrace, layers: dict, window=None, devices=None,
         top: int = 10) -> Reading:
    """What the program's spans say about ``window`` (default: the
    benchmark's window span) on ``devices`` (default: every device plane
    of the trace)."""
    lo, hi = window if window is not None else trace.window_of(pt.base)
    if devices is None:
        devices = sorted(pt.base.ops)
    pair = buckets(pt, lo, hi, devices, layers)
    bucket_s, bucket_layer_s = pair if pair else (None, None)
    return Reading(span_s=span_seconds(pt, lo, hi), bucket_s=bucket_s,
                   bucket_layer_s=bucket_layer_s,
                   idle_gaps=idle_gaps(pt, lo, hi, devices, top))
