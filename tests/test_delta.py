"""Incremental delta census: GraphDelta normalization, affected-dyad
exactness, apply_delta == full recompute bit-identity for every
registered op on all three backends (static + dynamic schedules), the
one-sync-per-delta regression pin, the delta_threshold cost-model
fallback, subscribed-session serving, the plan-cache-bounded task memo,
and a forced-8-device subprocess driving the delta pass through the real
work-queue pool."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (GraphDelta, affected_dyads, apply_delta_csr,
                        brute_force_census, canonical_dyads, from_edges,
                        generators, load_pajek_or_edgelist)
from repro.engine import (EngineConfig, GraphOp, PlanShapeError,
                          clear_plan_cache, compile, plan_cache_stats,
                          register_op)
from repro.engine.ops import make_census_batch_fn, unregister_op
from repro.serve import CensusService, ServiceConfig

BACKENDS = ["xla", "pallas", "distributed"]
ALL_OPS = ("triad_census", "dyad_census", "degree_stats", "triadic_profile")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")
BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def _cfg(backend, **kw):
    kw.setdefault("batch", 16)
    kw.setdefault("chunk_dyads", 64)
    kw.setdefault("delta_threshold", 1.0)  # always exercise the delta path
    return EngineConfig(backend=backend, **kw)


def _arcs(g):
    out_ptr = np.asarray(g.arrays.out_ptr)[: g.n + 1]
    dst = np.asarray(g.arrays.out_idx)[: g.m].astype(np.int64)
    src = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(out_ptr))
    return src, dst


def _rand_delta(g, rng, k_rem=3, k_add=3):
    src, dst = _arcs(g)
    rem = None
    if g.m and k_rem:
        sel = rng.choice(g.m, size=min(k_rem, g.m), replace=False)
        rem = np.stack([src[sel], dst[sel]], 1)
    add = rng.integers(0, g.n, size=(k_add, 2)) if k_add else None
    return GraphDelta(edges_added=add, edges_removed=rem)


def _assert_result_equal(got, want, ctx=""):
    assert type(got) is type(want), (ctx, got, want)
    for name, a, b in zip(type(got)._fields, got, want):
        if isinstance(a, np.ndarray):
            assert np.array_equal(a, b), (ctx, name, a, b)
        else:
            assert a == b, (ctx, name, a, b)


# ----------------------------------------------------------------------------
# GraphDelta normalization + validation (host layer)
# ----------------------------------------------------------------------------

def test_graph_delta_normalizes():
    d = GraphDelta(edges_added=[(1, 2), (2, 2), (1, 2), (3, 1)],
                   edges_removed=[(0, 1), (0, 1), (4, 4)])
    assert d.edges_added.shape == (2, 2)  # self-loop + duplicate dropped
    assert d.edges_removed.shape == (1, 2)
    assert d.size == 3 and not d.is_empty
    assert d.touched.tolist() == [0, 1, 2, 3]
    assert GraphDelta().is_empty and len(GraphDelta().touched) == 0


def test_graph_delta_rejects_bad_input():
    with pytest.raises(ValueError, match="edges_added"):
        GraphDelta(edges_added=[(1, 2, 3)])
    with pytest.raises(ValueError, match=">= 0"):
        GraphDelta(edges_removed=[(-1, 2)])
    g = from_edges(4, [0, 1], [1, 2])
    with pytest.raises(ValueError, match="n=4"):
        affected_dyads(g, GraphDelta(edges_added=[(0, 9)]))
    with pytest.raises(ValueError, match="n=4"):
        apply_delta_csr(g, GraphDelta(edges_removed=[(9, 0)]))


def test_apply_delta_csr_matches_rebuilt_graph():
    g = generators.rmat(5, edge_factor=4, seed=0)
    rng = np.random.default_rng(1)
    d = _rand_delta(g, rng, k_rem=4, k_add=4)
    g2 = apply_delta_csr(g, d)
    assert g2.n == g.n
    # oracle: mutate the arc list by hand and rebuild through from_edges
    src, dst = _arcs(g)
    key = src * g.n + dst
    rem = d.edges_removed[:, 0] * g.n + d.edges_removed[:, 1]
    keep = ~np.isin(key, rem)
    want = from_edges(g.n, np.concatenate([src[keep], d.edges_added[:, 0]]),
                      np.concatenate([dst[keep], d.edges_added[:, 1]]))
    for f in ("n", "m", "m_nbr", "max_deg", "max_out_deg"):
        assert getattr(g2, f) == getattr(want, f), f
    for name in ("out_ptr", "out_idx", "nbr_ptr", "nbr_idx", "nbr_deg"):
        assert np.array_equal(np.asarray(getattr(g2.arrays, name)),
                              np.asarray(getattr(want.arrays, name))), name
    # removing absent arcs / adding present ones is a no-op
    src2, dst2 = _arcs(g2)
    same = apply_delta_csr(g2, GraphDelta(
        edges_added=np.stack([src2[:3], dst2[:3]], 1),
        edges_removed=[(g.n - 1, g.n - 2)] if not (
            (src2 == g.n - 1) & (dst2 == g.n - 2)).any() else None))
    assert same.m == g2.m


def test_affected_dyads_are_touched_incident_and_sorted():
    g = generators.rmat(6, edge_factor=4, seed=2)
    d = GraphDelta(edges_added=[(3, 7)], edges_removed=[(10, 11)])
    u, v = affected_dyads(g, d)
    touched = set(d.touched.tolist())
    assert len(u) and (u < v).all()
    assert all(a in touched or b in touched for a, b in zip(u, v))
    # every canonical dyad incident to a touched vertex is present
    cu, cv = canonical_dyads(g)
    inc = [(a, b) for a, b in zip(cu.tolist(), cv.tolist())
           if a in touched or b in touched]
    assert sorted(zip(u.tolist(), v.tolist())) == sorted(inc)
    key = u.astype(np.int64) * g.n + v
    assert (np.diff(key) > 0).all()  # deterministic sorted order


# ----------------------------------------------------------------------------
# bit-identity: apply_delta == full recompute, every op, every backend
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("schedule", ["static", "dynamic"])
def test_apply_delta_bit_identical_to_full(backend, schedule):
    g = generators.rmat(6, edge_factor=4, seed=3)
    plan = compile(g, ALL_OPS, _cfg(backend, schedule=schedule))
    raw = plan.run_raw(g)
    rng = np.random.default_rng(7)
    cur = g
    for step in range(3):
        d = _rand_delta(cur, rng)
        res = plan.apply_delta(cur, d, raw)
        assert res.mode == "delta", (step, res.affected_fraction)
        full = plan.run_raw(res.graph)
        assert np.array_equal(res.raw, full), (backend, schedule, step)
        want = plan.layout.finalize(full, res.graph)
        for name in ALL_OPS:
            _assert_result_equal(res.results[name], want[name],
                                 (backend, schedule, step, name))
        # and the oracle agrees (not just internal consistency)
        _assert_result_equal(
            res.results["triad_census"], brute_force_census(res.graph),
            (backend, schedule, step))
        cur, raw = res.graph, res.raw


@pytest.mark.parametrize("backend", BACKENDS)
def test_delta_degenerate_cases(backend):
    g = generators.rmat(5, edge_factor=3, seed=4)
    plan = compile(g, ALL_OPS, _cfg(backend))
    raw = plan.run_raw(g)

    # empty delta: zero-cost identity, no sync, still mode "delta"
    syncs = plan.stats["host_syncs"]
    res = plan.apply_delta(g, GraphDelta(), raw)
    assert res.mode == "delta" and res.affected_fraction == 0.0
    assert res.raw is raw and plan.stats["host_syncs"] == syncs

    # delete-all: the correction must drive every bin to the empty graph's
    src, dst = _arcs(g)
    wipe = GraphDelta(edges_removed=np.stack([src, dst], 1))
    res = plan.apply_delta(g, wipe, raw)
    assert res.graph.m == 0 and res.graph.n_dyads == 0
    assert np.array_equal(res.raw, plan.run_raw(res.graph))
    assert res.results["triad_census"].counts.sum() == \
        brute_force_census(res.graph).counts.sum()

    # resurrect from empty: every dyad of the new graph is affected
    back = GraphDelta(edges_added=np.stack([src, dst], 1))
    res2 = plan.apply_delta(res.graph, back, res.raw)
    assert res2.mode == "delta" and res2.affected_fraction == 1.0
    assert np.array_equal(res2.raw, raw)  # round trip: original bins back

    # add-then-remove in separate applications is also an exact round trip
    probe = GraphDelta(edges_added=[(0, g.n - 1), (g.n - 1, 0)])
    mid = plan.apply_delta(g, probe, raw)
    final = plan.apply_delta(
        mid.graph, GraphDelta(edges_removed=probe.edges_added), mid.raw)
    assert np.array_equal(final.raw, raw)


def test_apply_delta_on_pajek_graph(tmp_path):
    p = tmp_path / "toy.net"
    p.write_text("*Vertices 12\n*Arcs\n" + "\n".join(
        f"{a} {b}" for a, b in [(1, 2), (2, 3), (3, 1), (4, 5), (5, 6),
                                (6, 4), (7, 8), (9, 10), (11, 12), (1, 7)]))
    g = load_pajek_or_edgelist(str(p))
    plan = compile(g, ALL_OPS, _cfg("xla"))
    raw = plan.run_raw(g)
    res = plan.apply_delta(g, GraphDelta(edges_added=[(0, 8), (8, 0)],
                                         edges_removed=[(0, 1)]), raw)
    assert res.mode == "delta"
    assert np.array_equal(res.raw, plan.run_raw(res.graph))
    _assert_result_equal(res.results["triad_census"],
                         brute_force_census(res.graph))


def test_random_mutation_sequence_stays_exact():
    """Deterministic long-stream soak: 12 mixed mutations, raw bins never
    drift from the full recompute (the invariant hypothesis fuzzes below)."""
    g = generators.rmat(6, edge_factor=3, seed=5)
    plan = compile(g, ALL_OPS, _cfg("xla"))
    raw = plan.run_raw(g)
    rng = np.random.default_rng(11)
    cur = g
    for step in range(12):
        d = _rand_delta(cur, rng, k_rem=int(rng.integers(0, 5)),
                        k_add=int(rng.integers(0, 5)))
        res = plan.apply_delta(cur, d, raw)
        cur, raw = res.graph, res.raw
    assert np.array_equal(raw, plan.run_raw(cur))


def test_property_random_mutations_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    g0 = generators.rmat(5, edge_factor=3, seed=6)
    plan = compile(g0, ALL_OPS, _cfg("xla"))
    base_raw = plan.run_raw(g0)
    edge = st.tuples(st.integers(0, g0.n - 1), st.integers(0, g0.n - 1))

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.lists(edge, max_size=4),
                              st.lists(edge, max_size=4)),
                    min_size=1, max_size=4))
    def prop(seq):
        p, cur, raw = plan, g0, base_raw
        for add, rem in seq:
            d = GraphDelta(edges_added=add or None,
                           edges_removed=rem or None)
            try:
                res = p.apply_delta(cur, d, raw)
                cur, raw = res.graph, res.raw
            except PlanShapeError:
                # the arcs outgrew the plan's buckets: recompile at the
                # new shape and reseed, as CensusService.mutate does
                cur = apply_delta_csr(cur, d)
                p = compile(cur, ALL_OPS, _cfg("xla"))
                raw = p.run_raw(cur)
        assert np.array_equal(raw, p.run_raw(cur))

    prop()


# ----------------------------------------------------------------------------
# sync accounting + cost-model fallback + opt-out
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_delta_costs_exactly_one_sync(backend):
    g = generators.rmat(6, edge_factor=4, seed=8)
    plan = compile(g, ALL_OPS, _cfg(backend))
    raw = plan.run_raw(g)
    d = _rand_delta(g, np.random.default_rng(0))
    before = plan.stats["host_syncs"]
    res = plan.apply_delta(g, d, raw)
    assert res.mode == "delta"
    assert plan.stats["host_syncs"] - before == 1, backend
    assert plan.stats["delta_runs"] == 1 and plan.stats["delta_fulls"] == 0


def test_delta_threshold_falls_back_to_full():
    g = generators.rmat(5, edge_factor=4, seed=9)
    plan = compile(g, ("triad_census",), _cfg("xla", delta_threshold=0.01))
    raw = plan.run_raw(g)
    d = _rand_delta(g, np.random.default_rng(1), k_rem=8, k_add=8)
    res = plan.apply_delta(g, d, raw)
    assert res.mode == "full" and res.affected_fraction > 0.01
    assert np.array_equal(res.raw, plan.run_raw(res.graph))
    assert plan.stats["delta_fulls"] == 1
    # raw=None also forces the full path regardless of footprint
    plan2 = compile(g, ("triad_census",), _cfg("xla"))
    res2 = plan2.apply_delta(g, GraphDelta(edges_added=[(0, 1)]))
    assert res2.mode == "full"
    assert np.array_equal(res2.raw, plan2.run_raw(res2.graph))


def test_sync_baseline_takes_full_path():
    g = generators.rmat(5, edge_factor=3, seed=10)
    plan = compile(g, ("triad_census",), _cfg("xla", device_accum=False))
    raw = plan.run_raw(g)
    res = plan.apply_delta(g, GraphDelta(edges_added=[(0, 1)]), raw)
    assert res.mode == "full"
    assert np.array_equal(res.raw, plan.run_raw(res.graph))


def test_non_local_op_forces_full_path():
    class NonLocal(GraphOp):
        name = "_nonlocal_probe"
        bins = 16
        kernel_key = "triad_census"  # reuse the census kernel/slice
        delta_local = False          # ...but claim a wider data horizon

        def make_batch_fn(self, meta, config):
            return make_census_batch_fn(meta.k, meta.member_iters,
                                        config.acc_jnp_dtype)

        def finalize(self, raw, g):
            return int(np.asarray(raw).sum())

    register_op(NonLocal())
    try:
        g = generators.rmat(5, edge_factor=3, seed=12)
        plan = compile(g, ("triad_census", "_nonlocal_probe"), _cfg("xla"))
        raw = plan.run_raw(g)
        res = plan.apply_delta(g, GraphDelta(edges_added=[(0, 2)]), raw)
        assert res.mode == "full"
        assert np.array_equal(res.raw, plan.run_raw(res.graph))
    finally:
        unregister_op("_nonlocal_probe")


def test_growth_past_buckets_raises_plan_shape_error():
    g = from_edges(16, [0, 1, 2], [1, 2, 3])
    plan = compile(g, ("triad_census",), _cfg("xla"))
    raw = plan.run_raw(g)
    hub = GraphDelta(edges_added=np.stack(
        [np.zeros(15, np.int64), np.arange(1, 16)], 1))
    with pytest.raises(PlanShapeError):
        plan.apply_delta(g, hub, raw)


def test_delta_threshold_validated():
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="delta_threshold"):
            EngineConfig(delta_threshold=bad)
    assert EngineConfig(delta_threshold=1).delta_threshold == 1.0
    with pytest.raises(ValueError, match="max_sessions"):
        ServiceConfig(max_sessions=0)


# ----------------------------------------------------------------------------
# subscribed evolving-graph sessions (serve layer)
# ----------------------------------------------------------------------------

def _svc(**census_kw):
    return CensusService(ServiceConfig(
        census=_cfg("xla", **census_kw), max_sessions=2))


def test_session_mutate_poll_cycle():
    svc = _svc()
    g = generators.rmat(6, edge_factor=4, seed=13)
    sid = svc.subscribe(g, ops=("triad_census", "degree_stats"))
    rng = np.random.default_rng(2)
    for _ in range(3):
        ack = svc.mutate(sid, _rand_delta(svc._sessions[sid].graph, rng,
                                          k_rem=2, k_add=2))
        assert ack["mode"] == "delta"
    cur = svc._sessions[sid].graph
    res = svc.poll(sid)
    want = compile(cur, ("triad_census", "degree_stats"),
                   svc.config.census).run(cur)
    _assert_result_equal(res["triad_census"], want["triad_census"])
    _assert_result_equal(res["degree_stats"], want["degree_stats"])
    st = svc.stats()["sessions"][sid]
    assert st["mutations"] == 3 and st["deltas"] == 3 and st["fulls"] == 0
    # single-op sessions poll the bare result; unsubscribe frees the slot
    sid2 = svc.subscribe(cur)
    _assert_result_equal(svc.poll(sid2), brute_force_census(cur))
    final = svc.unsubscribe(sid2)
    _assert_result_equal(final, brute_force_census(cur))
    assert sid2 not in svc.stats()["sessions"]
    with pytest.raises(KeyError, match="unknown session"):
        svc.poll(sid2)


def test_session_limit_and_stateless_poll_coexist():
    svc = _svc()
    g = generators.rmat(5, edge_factor=3, seed=14)
    svc.subscribe(g)
    svc.subscribe(g)
    with pytest.raises(RuntimeError, match="max_sessions"):
        svc.subscribe(g)
    # the stateless request stream is unaffected by live sessions
    rid = svc.submit(g)
    done = svc.flush()
    assert [c.request_id for c in done] == [rid]
    assert svc.poll() == []  # no-arg poll keeps its drain semantics


def _benchlib():
    """The benchmark's generator, plain reference and mutation stream:
    host code that shares nothing with the program."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from benchlib import generators as bench_generators
    from benchlib import reference
    from benchlib.mutations import Stream
    return bench_generators, reference, Stream


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_session_follows_the_held_back_stream_exactly(backend):
    """The benchmark's session on a small Kronecker graph: 12 batches of
    two arcs in and two out, drawn from a held-back pool; every poll
    equals the plain reference of the arc list the stream tracked."""
    bench_generators, reference, Stream = _benchlib()
    n, src, dst = bench_generators.kronecker(6, 8, seed=0)
    stream = Stream(n, src, dst, 32, np.random.default_rng(3))
    svc = CensusService(ServiceConfig(census=_cfg(backend)))
    sid = svc.subscribe(from_edges(*stream.arcs()))
    modes = []
    for _ in range(12):
        added, removed = stream.swap(*stream.draw(2, 2))
        ack = svc.mutate(sid, GraphDelta(edges_added=added,
                                         edges_removed=removed))
        modes.append(ack["mode"])
        assert ack["m"] == len(src) - 32
        assert np.array_equal(svc.poll(sid).counts,
                              reference.triad_census(*stream.arcs()))
    assert modes.count("delta") >= 10 and set(modes) <= {"delta",
                                                         "recompile"}
    assert svc._sessions[sid].plan.backend == backend


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_delta_counters_match_the_affected_sets_and_the_schedule(backend):
    from repro.engine.delta import _pallas_subset_schedule, _subset_tasks
    g = generators.rmat(6, edge_factor=4, seed=3)
    plan = compile(g, ("triad_census",), _cfg(backend))
    raw = plan.run_raw(g)
    d = _rand_delta(g, np.random.default_rng(5))
    before = dict(plan.stats)
    res = plan.apply_delta(g, d, raw)
    assert res.mode == "delta" and plan.backend == backend
    sets = [affected_dyads(g, d), affected_dyads(res.graph, d)]
    if backend == "pallas":
        tasks = [_pallas_subset_schedule(plan, x, u, v)[2]
                 for x, (u, v) in zip((g, res.graph), sets)]
    else:
        tasks = [_subset_tasks(plan, x, u, v, plan.chunk)
                 for x, (u, v) in zip((g, res.graph), sets)]
    assert (plan.stats["delta_affected"] - before["delta_affected"]
            == sum(len(u) for u, _ in sets) > 0)
    assert (plan.stats["delta_chunks"] - before["delta_chunks"]
            == sum(len(t) for t in tasks) > 0)
    entry, = plan_cache_stats()["entries"]
    assert entry["delta_affected"] == plan.stats["delta_affected"]
    assert entry["delta_chunks"] == plan.stats["delta_chunks"]


def test_session_recompile_on_bucket_outgrowth():
    svc = _svc()
    g = from_edges(32, [0, 1, 2], [1, 2, 3])
    sid = svc.subscribe(g)
    hub = GraphDelta(edges_added=np.stack(
        [np.zeros(20, np.int64), np.arange(1, 21)], 1))
    ack = svc.mutate(sid, hub)
    assert ack["mode"] == "recompile" and ack["m"] == 22
    cur = svc._sessions[sid].graph
    _assert_result_equal(svc.poll(sid), brute_force_census(cur))
    # the recompiled session keeps taking deltas on its new plan
    ack2 = svc.mutate(sid, GraphDelta(edges_removed=[(0, 20)]))
    assert ack2["mode"] == "delta"
    cur = svc._sessions[sid].graph
    _assert_result_equal(svc.poll(sid), brute_force_census(cur))
    st = svc.stats()["sessions"][sid]
    assert st["recompiles"] == 1 and st["deltas"] == 1


# ----------------------------------------------------------------------------
# satellite: the task-memo's lifetime is tied to the plan cache
# ----------------------------------------------------------------------------

def test_task_memo_bounded_and_cleared_with_plan_cache():
    g = generators.rmat(6, edge_factor=4, seed=15)
    plan = compile(g, ("triad_census",), _cfg("pallas"))
    plan.run(g)
    assert len(plan._task_memo) == 1  # the host-derived bucket schedule
    entry = plan_cache_stats()["entries"][-1]
    assert entry["task_memo"] == 1
    # memo stays bounded across many distinct graphs (same bucket only)
    for s in range(10):
        gg = generators.rmat(6, edge_factor=4, seed=100 + s)
        if gg.max_deg > plan.meta.k:
            continue  # would need a recompile; irrelevant to the memo
        plan.run(gg)
    assert len(plan._task_memo) <= 8
    clear_plan_cache()
    assert len(plan._task_memo) == 0  # lifetime tied to the cache


# ----------------------------------------------------------------------------
# the real pool: delta pass under forced 8 host devices (subprocess — the
# flag must be set before jax initializes; mirrors test_executor.py)
# ----------------------------------------------------------------------------

def test_delta_under_forced_device_pool():
    code = """
import numpy as np, jax
assert len(jax.devices()) == 8
from repro.core import GraphDelta, generators
from repro.engine import EngineConfig, compile
g = generators.rmat(7, edge_factor=4, seed=16)
ops = ("triad_census", "dyad_census", "degree_stats", "triadic_profile")
for backend in ("xla", "pallas"):
    plan = compile(g, ops, EngineConfig(backend=backend, batch=16,
                                        chunk_dyads=64, schedule="dynamic",
                                        delta_threshold=1.0))
    raw = plan.run_raw(g)
    assert plan.executor.n_devices == 8
    rng = np.random.default_rng(0)
    add = rng.integers(0, g.n, size=(6, 2))
    res = plan.apply_delta(g, GraphDelta(edges_added=add), raw)
    assert res.mode == "delta", backend
    assert np.array_equal(res.raw, plan.run_raw(res.graph)), backend
print('OK')
"""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
