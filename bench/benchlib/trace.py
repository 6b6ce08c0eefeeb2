"""From a profiler trace to the numbers the per-layer metrics read.

A trace is read in two steps, so that a test can check the second one on
a recorded trace without a chip:

* :func:`load` reads an ``.xplane.pb`` file (``jax.profiler.ProfileData``)
  into plain records: per device, the device operations with their start,
  duration and name; and the host spans the benchmark opened (names that
  start with ``bench.``).
* :func:`reduce` turns those records into busy time per device (the union
  of the operation intervals inside the window), device time by operation
  name and by layer, and the idle gaps with the host span that covers
  each.

Which device operations belong to a layer is data: ``bench/layers/*.json``
(see :func:`load_layers`).  An operation is named by its HLO text, and
by the program (XLA module) it ran in, which the trace's module line
gives.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import json
import os
import re
import shutil

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    """One device operation: its HLO text as the trace names it, and the
    name of the module (compiled program) it ran in."""
    start_ns: float
    dur_ns: float
    name: str
    module: str = ""


@dataclasses.dataclass
class Trace:
    ops: dict          # device plane name -> [Op]
    spans: list        # [(name, start_ns, end_ns)] host spans, bench.* only


def _device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


def _in_modules(ops: list, modules: list) -> None:
    """Name each op's module: the module event that holds its start."""
    modules.sort()
    ops.sort(key=lambda o: o.start_ns)
    i = 0
    for op in ops:
        while i < len(modules) and modules[i][1] < op.start_ns:
            i += 1
        if i < len(modules) and modules[i][0] <= op.start_ns:
            op.module = modules[i][2]


def load(path: str = None, *, data=None) -> Trace:
    """Read a ``.xplane.pb`` file (or a ``ProfileData``) into a
    :class:`Trace`."""
    if data is None:
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
    ops: dict = {}
    spans: list = []
    for plane in data.planes:
        if _device_plane(plane.name):
            got = ops.setdefault(plane.name, [])
            modules = []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules = [(float(e.start_ns),
                                float(e.start_ns + e.duration_ns),
                                e.name.split("(")[0]) for e in line.events]
                elif line.name == OP_LINE:
                    got.extend(Op(float(e.start_ns), float(e.duration_ns),
                                  e.name) for e in line.events)
            _in_modules(got, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name.split("#")[0], float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    return Trace(ops=ops, spans=spans)


def load_layers(directory: str) -> dict:
    """Layer name -> list of ``(module regex, op regex)``, from
    ``<directory>/*.json``.

    Each file is ``{"layer": <name>, "module": <regex>, "op": <regex>}``:
    an operation belongs to the layer when, for one file of that layer,
    the module regex matches the name of the module it ran in and the op
    regex matches its HLO text (``re.search``).  A program that renames a
    kernel or a module gets a new file beside the old one.
    """
    layers: dict = {}
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        layers.setdefault(spec["layer"], []).append(
            (re.compile(spec["module"]), re.compile(spec["op"])))
    return layers


def in_layer(op: Op, patterns) -> bool:
    return any(m.search(op.module) and o.search(op.name)
               for m, o in patterns)


def _union(intervals):
    """Sorted, merged ``[(start, end)]`` of a list of intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


@dataclasses.dataclass
class Reduction:
    window_s: float            # length of the traced window
    busy_s: float              # union of device op time, mean over devices
    devices: int               # devices the run uses
    layer_s: dict              # layer -> device seconds, summed over devices
    top_ops: list              # [(name, seconds)], most time first
    idle_gaps: list            # [(host span, seconds)], most time first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_of(trace: Trace):
    """``(start, end)`` of the benchmark's window span in trace time."""
    wins = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(wins)}")
    return wins[0]


class _Labels:
    """The innermost host span that covers a point: the latest-starting
    of the spans that hold it (the benchmark's spans nest, one thread)."""

    LOOK_BACK = 8

    def __init__(self, spans):
        self.spans = sorted((s, e, n) for n, s, e in spans
                            if n != WINDOW_SPAN)
        self.starts = [s for s, _, _ in self.spans]

    def __call__(self, lo, hi) -> str:
        mid = 0.5 * (lo + hi)
        i = bisect.bisect_right(self.starts, mid) - 1
        for s, e, name in reversed(self.spans[max(0, i - self.LOOK_BACK):
                                              i + 1]):
            if s <= mid <= e:
                return name
        return "outside any bench span"


def short_name(op: Op) -> str:
    """``<module> <op name> = <shape>``: the HLO text up to its layout."""
    return f"{op.module} {op.name.split('{')[0].strip()}"


def device_plane(device_id: int) -> str:
    """The trace's plane name of the device JAX numbers ``device_id``."""
    return f"/device:TPU:{device_id}"


def reduce(trace: Trace, layers: dict, window=None, devices=None,
           top: int = 10) -> Reduction:
    """Busy and idle time, device time by layer and by operation, and idle
    gaps by host span, inside ``window`` (default: the window span).

    ``devices`` names the planes of the chips the run uses (default: every
    device plane of the trace); other planes are left out.  A chip the run
    uses that ran nothing in the window counts as idle all through it, so
    the mean over chips shows a chip that stalled."""
    lo, hi = window if window is not None else window_of(trace)
    if devices is None:
        devices = sorted(trace.ops)
    busy = []
    per_name = collections.Counter()
    layer_ns = collections.Counter()
    gaps = collections.Counter()
    label = _Labels(trace.spans)
    for plane in sorted(devices):
        inside = []
        for op in trace.ops.get(plane, []):
            s, e = _clip(op.start_ns, op.start_ns + op.dur_ns, lo, hi)
            if e <= s:
                continue
            inside.append((s, e))
            per_name[short_name(op)] += e - s
            for layer, patterns in layers.items():
                if in_layer(op, patterns):
                    layer_ns[layer] += e - s
        merged = _union(inside)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[label(a, b)] += b - a
    n_dev = len(busy)
    if not any(busy):
        raise ValueError("no device operation inside the window")
    ns = 1e-9
    return Reduction(
        window_s=(hi - lo) * ns,
        busy_s=sum(busy) / n_dev * ns,
        devices=n_dev,
        layer_s={k: v * ns for k, v in layer_ns.items()},
        top_ops=[(k, v * ns) for k, v in per_name.most_common(top)],
        idle_gaps=[(k, v / n_dev * ns) for k, v in gaps.most_common(top)])


@contextlib.contextmanager
def capture(directory: str):
    """Trace the device and the benchmark's host spans into ``directory``
    (emptied first); yields a list that holds the ``.xplane.pb`` path once
    the block has ended.  The Python function tracer stays off."""
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out: list = []
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file in {directory}, "
                           f"found {found}")
    out.append(found[0])
