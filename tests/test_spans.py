"""The engine's spans and counters: a small census on the pallas path
(interpret mode on CPU) under ``jax.profiler.trace`` opens every
``repro.*`` span, nested as documented, and its counters agree with the
plan's own chunk schedule; so does one mutation of a subscribed
session.  The spans' tally (``span_totals``) counts nested spans and
spans opened on other threads."""
import dataclasses
import glob

import numpy as np
import pytest

import jax
from repro.core import generators
from repro.core.census import canonical_dyads
from repro.core.delta import affected_dyads
from repro.core.graph import arcs_host, from_edges
from repro.engine import EngineConfig, compile

# small chunks, so one census dispatches more chunks than the pipeline
# depth and ``repro.wait`` opens too
CONFIG = EngineConfig(backend="pallas", batch=32, chunk_dyads=64,
                      buckets=(4, 8))

# span -> the span it opens inside (None: outside every repro.* span)
NESTING = {"repro.from_edges": None, "repro.compile": None,
           "repro.run": None, "repro.stage": "repro.run",
           "repro.enumerate": "repro.run", "repro.schedule": "repro.run",
           "repro.chunk": "repro.run", "repro.wait": "repro.run",
           "repro.fetch": "repro.run", "repro.finalize": None}


def _arcs():
    g = generators.rmat(6, edge_factor=4, seed=3)
    return (g.n, *arcs_host(g))


def _census(arcs, config=CONFIG):
    g = from_edges(*arcs)
    plan = compile(g, ["triad_census"], config)
    return g, plan, plan.run(g)["triad_census"].counts


def _host_events(out):
    """Every ``repro.*`` host event of the trace written under ``out``:
    ``[(name, start, end, stats, line)]``."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats), (plane.name, i))
                       for e in line.events if e.name.startswith("repro.")]
    return events


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Every ``repro.*`` host event of one warm census."""
    arcs = _arcs()
    _census(arcs)                    # compile outside the trace
    out = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(out):
        _census(arcs)
    return _host_events(out)


def _parent(event, events):
    """The innermost other repro.* span on the same thread holding
    ``event``, or None."""
    name, s, e, _, line = event
    holders = [h for h in events if h is not event and h[4] == line
               and h[1] <= s and e <= h[2]]
    return max(holders, key=lambda h: h[1])[0] if holders else None


def test_every_span_opens_nested_as_documented(traced):
    names = {e[0] for e in traced}
    assert names == set(NESTING)
    for event in traced:
        assert _parent(event, traced) == NESTING[event[0]], event
    runs = [e for e in traced if e[0] == "repro.run"]
    assert len(runs) == 1 and runs[0][3]["run"] >= 1
    compiles = [e for e in traced if e[0] == "repro.compile"]
    assert [c[3]["hit"] for c in compiles] == [1]


def test_chunk_spans_carry_their_bucket_and_bounds(traced):
    chunks = [e[3] for e in traced if e[0] == "repro.chunk"]
    assert len(chunks) > CONFIG.pipeline_depth
    assert all({"K", "start", "end"} <= set(c) for c in chunks)
    assert {c["K"] for c in chunks} <= {4, 8, 16, 32, 64}
    assert all(c["start"] < c["end"] for c in chunks)


def _blocks(tasks, chunk: int) -> int:
    """Aligned 128-lane blocks the two tiles of every task fetch."""
    return sum(2 * chunk * (-(-t.key // 128) + 1) for t in tasks)


def _probe_columns(g, u, v) -> int:
    """Σ min(deg u, deg v) over the dyads, from the arc list itself."""
    nbrs = [set() for _ in range(g.n)]
    for a, b in zip(*arcs_host(g)):
        nbrs[a].add(b)
        nbrs[b].add(a)
    return sum(min(len(nbrs[a]), len(nbrs[b])) for a, b in zip(u, v))


def test_counters_agree_with_the_plans_own_schedule():
    arcs = _arcs()
    _, plan, _ = _census(arcs)
    before = dict(plan.stats)
    g, _, _ = _census(arcs)          # a fresh graph: the memo misses
    (tasks, probe), = [ts for ref, ts in plan._task_memo.values()
                       if ref() is g]
    chunk = max(CONFIG.resolve_block(),
                plan.chunk // CONFIG.resolve_block()
                * CONFIG.resolve_block())
    delta = {k: plan.stats[k] - before[k]
             for k in ("tile_slots", "gather_blocks", "dyads",
                       "probe_columns", "bytes_staged", "task_memo_hits",
                       "task_memo_misses")}
    assert delta["tile_slots"] == sum(2 * chunk * t.key for t in tasks)
    assert delta["probe_columns"] == probe == _probe_columns(
        g, *canonical_dyads(g))
    assert delta["gather_blocks"] == _blocks(tasks, chunk)
    assert delta["dyads"] == sum(min(t.end, t.start + chunk) - t.start
                                 for t in tasks) == g.n_dyads
    assert delta["bytes_staged"] == sum(
        a.nbytes for a in plan.padded_arrays_host(g) if a is not None)
    assert (delta["task_memo_hits"], delta["task_memo_misses"]) == (0, 1)
    plan.run(g)                      # the same graph again: a memo hit
    assert plan.stats["task_memo_hits"] - before["task_memo_hits"] == 1


def test_a_warm_pallas_census_adds_no_trace():
    arcs = _arcs()
    fresh = dataclasses.replace(CONFIG, buckets=(2, 8))  # a plan of its own
    _, plan, first = _census(arcs, fresh)
    assert plan.backend == "pallas" and plan.stats["traces"] > 0
    traces = plan.stats["traces"]
    _, again, second = _census(arcs, fresh)
    assert again is plan and plan.stats["traces"] == traces
    assert np.array_equal(first, second)


def test_gather_blocks_count_a_delta_pass(monkeypatch):
    """A delta census (two subset passes) counts the blocks of the tasks
    it dispatches, as the full pass does."""
    from repro.core import GraphDelta
    config = dataclasses.replace(CONFIG, delta_threshold=1.0)
    arcs = _arcs()
    g, plan, _ = _census(arcs, config)
    raw = plan.run_raw(g)
    chunk = max(config.resolve_block(),
                plan.chunk // config.resolve_block()
                * config.resolve_block())
    dispatched = []
    run = plan.executor.run

    def spy(tasks, **kw):
        dispatched.extend(tasks)
        return run(tasks, **kw)

    monkeypatch.setattr(plan.executor, "run", spy)
    before = dict(plan.stats)
    delta = GraphDelta(edges_added=[(0, 9), (3, 17)],
                       edges_removed=[(int(arcs[1][0]), int(arcs[2][0]))])
    res = plan.apply_delta(g, delta, raw)
    assert res.mode == "delta" and dispatched
    assert plan.stats["gather_blocks"] - before["gather_blocks"] == _blocks(
        dispatched, chunk)
    assert plan.stats["tile_slots"] - before["tile_slots"] == sum(
        2 * chunk * t.key for t in dispatched)
    # both subset passes walk the short row of every affected dyad
    want = sum(_probe_columns(graph, *affected_dyads(graph, delta))
               for graph in (g, res.graph))
    assert plan.stats["probe_columns"] - before["probe_columns"] == want


# span -> the span it opens inside, for one subscribed mutation on the
# delta path
DELTA_NESTING = {"repro.mutate": None, "repro.run": "repro.mutate",
                 "repro.delta": "repro.run",
                 "repro.apply_csr": "repro.delta",
                 "repro.from_edges": "repro.apply_csr",
                 "repro.affected": "repro.delta",
                 "repro.stage": "repro.delta",
                 "repro.delta_schedule": "repro.delta",
                 "repro.chunk": "repro.delta", "repro.wait": "repro.delta",
                 "repro.delta_fold": "repro.delta",
                 "repro.fetch": "repro.delta_fold"}


def test_a_mutation_opens_the_delta_spans_nested_as_documented(tmp_path):
    from repro.core import GraphDelta, affected_dyads
    from repro.serve import CensusService, ServiceConfig
    n, src, dst = _arcs()
    svc = CensusService(ServiceConfig(
        census=dataclasses.replace(CONFIG, delta_threshold=1.0)))
    sid = svc.subscribe(from_edges(n, src, dst))
    warm = GraphDelta(edges_added=[(0, 9)],
                      edges_removed=[(int(src[0]), int(dst[0]))])
    svc.mutate(sid, warm)            # compile outside the trace
    old = svc._sessions[sid].graph
    delta = GraphDelta(edges_added=[(3, 17), (5, 40)],
                       edges_removed=[(int(src[1]), int(dst[1]))])
    with jax.profiler.trace(str(tmp_path)):
        ack = svc.mutate(sid, delta)
    events = _host_events(str(tmp_path))
    names = {e[0] for e in events}
    assert set(DELTA_NESTING) - {"repro.wait"} <= names <= set(DELTA_NESTING)
    for event in events:
        assert _parent(event, events) == DELTA_NESTING[event[0]], event
    assert ack["mode"] == "delta"
    mutate, = [e[3] for e in events if e[0] == "repro.mutate"]
    assert mutate["mode"] == "delta"
    stats, = [e[3] for e in events if e[0] == "repro.delta"]
    new = svc._sessions[sid].graph
    assert stats["affected_old"] == len(affected_dyads(old, delta)[0])
    assert stats["affected_new"] == len(affected_dyads(new, delta)[0])
    assert len([e for e in events if e[0] == "repro.affected"]) == 2


def test_span_totals_count_nested_spans_and_threads():
    """Nested spans count each under its name; spans closed on more
    threads than cores, switching as often as the interpreter allows,
    lose no call."""
    import os
    import sys
    from concurrent.futures import ThreadPoolExecutor, wait

    from repro.core.spans import span, span_totals
    before = span_totals()
    with span("tally_outer"):
        for _ in range(2):
            with span("tally_inner"):
                pass

    def opened(_):
        for _ in range(50):
            with span("tally_thread", i=1):
                pass
    workers = (os.cpu_count() or 1) + 2
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(workers) as pool:
            futures = [pool.submit(opened, i) for i in range(2 * workers)]
            done, _ = wait(futures, timeout=120)
            assert len(done) == len(futures)
            for f in futures:
                f.result()
    finally:
        sys.setswitchinterval(interval)
    after = span_totals()

    def window(name):
        s0, c0 = before.get(name, (0.0, 0))
        s1, c1 = after[name]
        return s1 - s0, c1 - c0
    assert [window(k)[1] for k in ("tally_outer", "tally_inner",
                                   "tally_thread")] == [1, 2,
                                                        100 * workers]
    assert window("tally_outer")[0] >= window("tally_inner")[0] > 0


def test_span_totals_count_every_chunk_of_a_census():
    from repro.core.spans import span_totals
    arcs = _arcs()
    _, plan, _ = _census(arcs)
    before, chunks = span_totals(), plan.stats["chunks"]
    _census(arcs)
    after = span_totals()
    assert (after["chunk"][1] - before["chunk"][1]
            == plan.stats["chunks"] - chunks > 0)
    assert after["run"][1] - before["run"][1] == 1


@pytest.mark.parametrize("fn_name,span_name", [
    ("affected_dyads", "affected"), ("apply_delta_csr", "apply_csr")])
def test_a_spanned_function_keeps_its_name_and_tallies_each_call(
        fn_name, span_name):
    from repro.core import GraphDelta
    from repro.core import delta as core_delta
    from repro.core.spans import span_totals
    fn = getattr(core_delta, fn_name)
    assert fn.__name__ == fn_name and fn.__doc__
    n, src, dst = _arcs()
    g = from_edges(n, src, dst)
    d = GraphDelta(edges_added=[(0, 9)],
                   edges_removed=[(int(src[0]), int(dst[0]))])
    calls = span_totals().get(span_name, (0.0, 0))[1]
    fn(g, d)
    fn(g, d)
    assert span_totals()[span_name][1] == calls + 2
