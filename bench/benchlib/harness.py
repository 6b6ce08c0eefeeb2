"""One run of one cell: set-up, the measured window, the check of every
answer against the plain reference, and the result line.

The loop of the cell's traffic (``bench/loops/<loop>.py``) provides
five functions, each given the :class:`Run`:

    setup(run) -> state            build the program's objects and warm up
                                   every shape the window will use
    window(run, state, deadline)   drive the program until ``deadline``
                                   (host clock); returns a Window
    end_to_end(run, state, win)    {metric name: value}
    counters(run, state, win)      {name: value} of the program's counters
                                   over the window, for per-layer readers
    answers(run, state, win)       [Answer]: what the window produced,
                                   each with the arc list it answers for
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import shutil
import time
import types

import numpy as np

from . import cells, generators, peaks, reference, trace

CACHE_DIR = os.path.join(cells.ROOT, ".jax_cache")
TRACE_DIR = os.path.join(cells.ROOT, ".bench_out", "trace")
MEMORY_KEYS = ("peak_bytes_in_use", "bytes_in_use", "bytes_limit",
               "largest_alloc_size")


class Fallback(RuntimeError):
    """The program did not run the path the cell measures."""


@dataclasses.dataclass
class Window:
    t0: float                 # host clock at the window's start
    t_end: float              # host clock at the last completion
    attempted: int            # requests, censuses or mutations started
    completed: int            # of them, answered
    data: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Answer:
    key: object               # same key, same graph
    arcs: object              # () -> (n, src, dst)
    got: object               # (16,) counts, or None: never answered


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.
    Every program is cached, however small, so that only a checkout's
    first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compilations and their seconds, from JAX's own events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


class Spans:
    """Host spans of the benchmark's own calls into the program: seconds
    by name, and a profiler annotation of the same name, so that a trace
    can say what the host was doing in each idle gap."""

    def __init__(self):
        self.seconds = collections.defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + name):
            yield
        self.seconds[name].append(time.perf_counter() - t)

    def reset(self):
        self.seconds.clear()


def check_plans(expected_backend: str) -> None:
    """Every plan the program holds runs ``expected_backend``, was never
    demoted, and lost no device to quarantine."""
    from repro.engine import plan_cache_stats
    entries = plan_cache_stats()["entries"]
    if not entries:
        raise Fallback("the program holds no plan")
    for e in entries:
        where = f"plan {e['meta']}"
        if e["backend"] != expected_backend:
            raise Fallback(f"{where} runs {e['backend']!r}, not "
                           f"{expected_backend!r}")
        if e["degradation"]:
            raise Fallback(f"{where} was demoted: {e['degradation']}")
        if e["faults"]["quarantines"]:
            raise Fallback(f"{where} quarantined "
                           f"{e['faults']['quarantines']} device(s)")


@dataclasses.dataclass
class Run:
    cell: cells.Cell
    seed: int
    expected_backend: str
    spans: Spans
    log: object = print
    data: object = None       # the configuration's graph(s), from the seed

    @property
    def traffic(self):
        return self.cell.traffic

    def engine_config(self):
        from repro.engine import EngineConfig
        cfg = EngineConfig(**self.traffic.get("engine", {}))
        if self.expected_backend == "pallas" and cfg.resolve_interpret():
            raise Fallback("the pallas kernels would run in interpret mode")
        return cfg


def compare(answers, control=None):
    """``(wrong, checked)``: answers that are missing or differ from the
    reference in any of the 16 counts.  ``control`` puts a census
    function in the program's place (the check's control)."""
    refs, subs = {}, {}
    wrong = 0
    for a in answers:
        if a.key not in refs:
            refs[a.key] = reference.triad_census(*a.arcs())
        got = a.got
        if control is not None:
            if a.key not in subs:
                subs[a.key] = control(*a.arcs())
            got = subs[a.key]
        if got is None or np.asarray(got).tolist() != refs[a.key].tolist():
            wrong += 1
    return wrong, len(answers)


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cells.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: cells.Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, expected_backend: str, control=None,
             compile_cache: bool = True, log=print) -> dict:
    """Run ``cell`` once and return the result line's object.

    With ``control`` (a census function), the answers are also judged
    with the control in the program's place, under the key ``control``;
    the benchmark's own runs never pass one.  ``compile_cache=False``
    leaves JAX's cache settings alone (tests in a shared process)."""
    import jax
    if compile_cache:
        use_compile_cache()
    clock = CompileClock()
    devices = jax.devices()[: cell.chips]
    loop = cells.load_module("loops", cell.traffic["loop"])
    run = Run(cell=cell, seed=seed, expected_backend=expected_backend,
              spans=Spans(), log=log)
    t_graph = time.perf_counter()
    run.data = generators.make_graph(cell.config["graph"], run.seed)
    t_loop = time.perf_counter()
    state = loop.setup(run)
    check_plans(expected_backend)
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    compiles_setup, compile_s = clock.count, clock.seconds
    run.spans.reset()
    with contextlib.ExitStack() as stack:
        path = stack.enter_context(trace.capture(TRACE_DIR)) if traced else []
        with run.spans("window"):
            win = loop.window(run, state, time.perf_counter() + seconds)
    compiles_window = clock.count - compiles_setup
    check_plans(expected_backend)
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    e2e = loop.end_to_end(run, state, win)
    counters = loop.counters(run, state, win)
    answers = loop.answers(run, state, win)
    del state
    log(f"setup_s {setup_s:.3f}: {compiles_setup} compiles, "
        f"{compile_s:.3f} s compiling; start {t_graph - t_start:.3f} s, "
        f"graph {t_loop - t_graph:.3f} s, program {t_window - t_loop:.3f} s")
    log(f"memory {[{k: s.get(k) for k in MEMORY_KEYS} for s in stats]}")
    log(f"compiles_in_window {compiles_window}")
    t = time.perf_counter()
    wrong, checked = compare(answers)
    log(f"reference checked {checked} answers in "
        f"{time.perf_counter() - t:.3f} s")
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": wrong == 0, "attempted": win.attempted,
              "failed": win.attempted - win.completed, "metrics": {},
              "device": device}
    if traced:
        red = trace.reduce(trace.load(path[0]),
                           trace.load_layers(os.path.join(cells.BENCH,
                                                          "layers")),
                           devices=[trace.device_plane(d.id)
                                    for d in devices])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = types.SimpleNamespace(
            trace=red, work=win.completed, counters=counters,
            spans=run.spans.seconds, peaks=peaks.peaks(kind))
        result["metrics"] = per_layer(cell, ctx)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in red.top_ops],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps]}
    else:
        e2e["setup_s"] = setup_s
        result["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    if control is not None:
        result["control"] = {"wrong_answers": compare(answers, control)[0],
                             "checked": checked}
    result["checks"] = {"wrong_answers": {"value": wrong, "limit": 0}}
    return result
