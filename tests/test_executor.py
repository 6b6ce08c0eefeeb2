"""Executor layer: cost-model chunk boundaries, dynamic-vs-static
bit-identity for every registered op on all three backends, the
single-sync regression pin (the pallas control fetch is gone), config
knob validation, schedule metadata in the plan cache, per-device
occupancy counters, and a forced-8-device subprocess exercising the real
work-queue pool."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import balance, brute_force_census, from_edges, generators
from repro.core.census import host_bucket_schedule, sort_dyads_by_bucket
from repro.engine import (EngineConfig, clear_plan_cache, compile,
                          list_ops, plan_cache_stats)
from repro.serve import CensusService, ServiceConfig

BACKENDS = ["xla", "pallas", "distributed"]
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def _assert_result_equal(got, want, ctx=""):
    assert type(got) is type(want), (ctx, got, want)
    for name, a, b in zip(type(got)._fields, got, want):
        if isinstance(a, np.ndarray):
            assert np.array_equal(a, b), (ctx, name, a, b)
        else:
            assert a == b, (ctx, name, a, b)


# ----------------------------------------------------------------------------
# cost-model chunk boundaries (core/balance.py driving the executor)
# ----------------------------------------------------------------------------

def test_chunk_bounds_cover_and_respect_capacity():
    rng = np.random.default_rng(0)
    w = rng.integers(1, 50, size=1000).astype(np.float64)
    b = balance.chunk_bounds_by_cost(w, 128)
    assert b[0] == 0 and b[-1] == len(w)
    spans = np.diff(b)
    assert (spans >= 1).all() and (spans <= 128).all()
    # equal-cost property: no chunk's predicted work dominates the run
    costs = np.add.reduceat(w, b[:-1])
    assert costs.max() <= 2 * costs.mean()


def test_chunk_bounds_heavy_items_get_small_chunks():
    # a heavy-degree region in an otherwise light stream: its chunks must
    # be shorter than the light region's (the paper's degree-aware load
    # shaping, applied to the chunk schedule).
    w = np.concatenate([np.ones(400), np.full(100, 100.0), np.ones(400)])
    b = balance.chunk_bounds_by_cost(w, 256)
    spans = np.diff(b)
    mids = (b[:-1] + b[1:]) // 2
    heavy = spans[(mids >= 400) & (mids < 500)]
    light = spans[mids < 400]
    assert heavy.max() < light.min()
    # a single task heavier than the quota still gets a chunk of its own
    b2 = balance.chunk_bounds_by_cost(np.array([1.0, 1e9, 1.0]), 8)
    assert (np.diff(b2) >= 1).all() and b2[-1] == 3


def test_chunk_bounds_degenerate():
    assert balance.chunk_bounds_by_cost(np.zeros(0), 4).tolist() == [0]
    assert balance.chunk_bounds_by_cost(np.zeros(5), 2).tolist() == [0, 2, 4, 5]
    with pytest.raises(ValueError, match="capacity"):
        balance.chunk_bounds_by_cost(np.ones(3), 0)


def test_host_bucket_schedule_matches_device_sort():
    """The host-derived bucket counts (which replaced the pallas control
    fetch) must equal the device sort's histogram exactly — the chunk
    schedule slices the device-sorted stream by them."""
    import jax.numpy as jnp

    from repro.core.census import enumerate_dyads_device

    for seed in (0, 5):
        g = generators.rmat(6, edge_factor=4, seed=seed)
        ks = tuple(sorted({min(k, max(g.max_deg, 1)) for k in (4, 16, 64)}
                          | {max(g.max_deg, 1)}))
        du, dv = enumerate_dyads_device(g.arrays.nbr_ptr, g.arrays.nbr_idx,
                                        jnp.int32(g.m_nbr),
                                        out_size=max(g.n_dyads, 1))
        _, _, counts_dev = sort_dyads_by_bucket(
            g.arrays.nbr_deg, du, dv, jnp.int32(g.n_dyads), ks=ks)
        counts, need_sorted = host_bucket_schedule(g, ks)
        assert counts.tolist() == np.asarray(counts_dev).tolist()
        assert counts.sum() == g.n_dyads == len(need_sorted)
        assert (np.diff(need_sorted) >= 0).sum() >= 0  # grouped-by-bucket


# ----------------------------------------------------------------------------
# dynamic == static bit-identity, every registered op, every backend
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_dynamic_schedule_bit_identical(backend):
    """Acceptance criterion: the dynamic work-queue schedule (over
    however many devices this process sees — the multi-device CI job
    forces 8) produces exactly the static single-device results for
    every registered op."""
    ops = list_ops()
    g = generators.rmat(6, edge_factor=4, seed=2)
    stat = compile(g, ops, EngineConfig(backend=backend, batch=16,
                                        chunk_dyads=64))
    dyn = compile(g, ops, EngineConfig(backend=backend, batch=16,
                                       chunk_dyads=64, schedule="dynamic"))
    a, b = stat.run(g), dyn.run(g)
    for name in ops:
        _assert_result_equal(a[name], b[name], ctx=(backend, name))
    assert (b["triad_census"].counts == brute_force_census(g).counts).all()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_dynamic_schedule_on_degree_skewed_graph(backend):
    """A star graph maximizes degree skew: the cost model must shrink
    chunks around the hub's dyads and the results must not move."""
    g = from_edges(40, [0] * 39 + list(range(1, 20)),
                   list(range(1, 40)) + [0] * 19)
    stat = compile(g, ("triad_census",),
                   EngineConfig(backend=backend, batch=16, chunk_dyads=32))
    dyn = compile(g, ("triad_census",),
                  EngineConfig(backend=backend, batch=16, chunk_dyads=32,
                               schedule="dynamic"))
    a = stat.run(g)["triad_census"]
    b = dyn.run(g)["triad_census"]
    assert (a.counts == b.counts).all()
    assert (a.counts == brute_force_census(g).counts).all()


def test_dynamic_batch_runs_bit_identical():
    fleet = [generators.rmat(6, edge_factor=4, seed=s) for s in (0, 1)]
    empty = from_edges(5, [], [])
    ops = ("triad_census", "degree_stats")
    dyn = compile(fleet[0], ops, EngineConfig(backend="xla", batch=16,
                                              chunk_dyads=64,
                                              schedule="dynamic"))
    batched = dyn.run_batch(fleet + [empty])
    for got, g in zip(batched, fleet + [empty]):
        want = dyn.run(g)
        for name in ops:
            _assert_result_equal(got[name], want[name], ctx=name)


# ----------------------------------------------------------------------------
# satellite: the pallas extra sync is gone — pin host_syncs == 1 everywhere
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_device_path_single_sync_regression_pin(backend):
    """Every backend's device-resident run costs exactly ONE counted
    device→host transfer.  The pallas backend used to pay 2 (a control
    fetch of the device sort's bucket counts — BENCH_census.json showed
    host_syncs_per_run: 2 while xla/distributed showed 1); the schedule
    is now derived host-side (census.py::host_bucket_schedule), so a
    regression reintroducing the fetch fails here."""
    g = generators.rmat(7, edge_factor=4, seed=3)
    for schedule in ("static", "dynamic"):
        plan = compile(g, ("triad_census",),
                       EngineConfig(backend=backend, batch=16,
                                    chunk_dyads=64, schedule=schedule))
        plan.run(g)
        runs = plan.stats["runs"]
        assert plan.stats["host_syncs"] == runs == 1, (backend, schedule,
                                                       plan.stats)
        plan.run(g)
        assert plan.stats["host_syncs"] == 2  # exactly one more per run


# ----------------------------------------------------------------------------
# satellite: EngineConfig numeric-knob validation
# ----------------------------------------------------------------------------

def test_numeric_knobs_validated_at_construction():
    with pytest.raises(ValueError, match="chunk_dyads must be >= 1"):
        EngineConfig(chunk_dyads=0)
    with pytest.raises(ValueError, match="chunk_dyads must be >= 1"):
        EngineConfig(chunk_dyads=-5)
    with pytest.raises(ValueError, match="pipeline_depth must be >= 1"):
        EngineConfig(pipeline_depth=0)
    with pytest.raises(ValueError, match="n_executor_devices must be >= 1"):
        EngineConfig(n_executor_devices=0)
    with pytest.raises(ValueError, match="n_executor_devices must be >= 1"):
        EngineConfig(n_executor_devices=-1)
    with pytest.raises(ValueError, match="schedule must be one of"):
        EngineConfig(schedule="adaptive")
    with pytest.raises(ValueError, match="batch must be >= 1"):
        EngineConfig(batch=0)
    with pytest.raises(ValueError, match="block must be >= 1"):
        EngineConfig(block=0)
    # the happy path stays hashable (the config is a plan-cache key)
    hash(EngineConfig(chunk_dyads=64, pipeline_depth=3,
                      schedule="dynamic", n_executor_devices=4))


# ----------------------------------------------------------------------------
# satellite: schedule metadata in the plan cache + device occupancy
# ----------------------------------------------------------------------------

def test_plan_cache_entries_carry_schedule_and_devices():
    import jax

    g = generators.rmat(6, edge_factor=4, seed=0)
    compile(g, ("triad_census",), EngineConfig(backend="xla", chunk_dyads=64))
    dyn = compile(g, ("triad_census",),
                  EngineConfig(backend="xla", chunk_dyads=64,
                               schedule="dynamic"))
    entries = plan_cache_stats()["entries"]
    assert [e["schedule"] for e in entries] == ["static", "dynamic"]
    assert entries[0]["n_devices"] == 1
    assert entries[1]["n_devices"] == len(jax.devices())
    # pool width asked beyond the visible device count is clamped, and
    # normalizes into the SAME cache entry as the all-devices default
    over = compile(g, ("triad_census",),
                   EngineConfig(backend="xla", chunk_dyads=64,
                                schedule="dynamic",
                                n_executor_devices=10_000))
    assert over is dyn
    assert over.executor.n_devices == len(jax.devices())


def test_device_chunk_occupancy_accounting():
    g = generators.rmat(6, edge_factor=4, seed=1)
    plan = compile(g, ("triad_census",),
                   EngineConfig(backend="xla", chunk_dyads=64,
                                schedule="dynamic"))
    plan.run(g)
    dc = plan.stats["device_chunks"]
    assert sum(dc.values()) == plan.stats["chunks"] > 0
    assert all(0 <= d < plan.executor.n_devices for d in dc)
    entry = plan_cache_stats()["entries"][0]
    assert entry["device_chunks"] == dc


def test_service_reports_per_device_occupancy():
    ops_sets = (("triad_census",), ("triad_census", "degree_stats"))
    svc = CensusService(ServiceConfig(
        max_batch=4, max_wait_requests=100,
        census=EngineConfig(backend="xla", batch=16, chunk_dyads=64,
                            schedule="dynamic")))
    fleet = [generators.rmat(6, edge_factor=4, seed=s) for s in range(4)]
    for i, g in enumerate(fleet):  # two (bucket, ops) groups
        svc.submit(g, ops=ops_sets[i % 2])
    done = svc.flush()
    assert len(done) == 4
    for c in done:
        _assert_result_equal(
            c.result["triad_census"] if isinstance(c.result, dict)
            else c.result,
            compile(fleet[c.request_id], ("triad_census",),
                    EngineConfig(backend="xla", batch=16, chunk_dyads=64)
                    ).run(fleet[c.request_id])["triad_census"])
    st = svc.stats()
    assert sum(st["devices"].values()) == sum(
        b["chunks"] for b in st["buckets"].values()) > 0


def test_service_static_schedule_keeps_device_zero():
    svc = CensusService(ServiceConfig(
        max_batch=2, census=EngineConfig(backend="xla", chunk_dyads=64)))
    svc.run_fleet([generators.rmat(6, edge_factor=4, seed=s)
                   for s in range(2)])
    st = svc.stats()
    assert set(st["devices"]) == {0}


# ----------------------------------------------------------------------------
# the real pool: forced 8 host devices in a subprocess (the flag must be
# set before jax initializes; the multi-device CI job runs the whole
# suite this way, this test guarantees coverage on 1-device hosts too)
# ----------------------------------------------------------------------------

def test_workqueue_spreads_over_forced_device_pool():
    code = """
import numpy as np, jax
assert len(jax.devices()) == 8
from repro.core import brute_force_census, generators
from repro.engine import EngineConfig, compile
g = generators.rmat(7, edge_factor=4, seed=11)
want = brute_force_census(g).counts
for backend in ("xla", "pallas"):
    dyn = compile(g, ("triad_census", "dyad_census"),
                  EngineConfig(backend=backend, batch=16, chunk_dyads=64,
                               schedule="dynamic"))
    res = dyn.run(g)
    assert (res["triad_census"].counts == want).all(), backend
    assert dyn.executor.n_devices == 8
    dc = dyn.stats["device_chunks"]
    assert sum(dc.values()) == dyn.stats["chunks"]
    assert len(dc) > 1, (backend, dc)  # the queue actually fanned out
    assert dyn.stats["host_syncs"] == 1  # one merged fetch, pool-wide
print('OK')
"""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
