"""Partitioned-graph subsystem: owned-dyad cuts and halo construction,
bit-identity of partitioned runs across partitions × backend × schedule
(one-sync pinned), star-graph halo coverage, partition × delta × fault ×
reorder cross composition, mmap/spill out-of-core budget, config knob
validation, partition metadata in plan_cache_stats / service stats, the
sharding.rules deprecation shim, and a forced-8-device subprocess."""
import importlib
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import brute_force_census, generators
from repro.core.delta import GraphDelta
from repro.core.graph import (GraphArrays, arcs_host, arcs_host_iter,
                              from_edges, from_edges_mmap)
from repro.core.partition import (build_local_arrays, partition_cuts,
                                  partition_graph, shard_dyads)
from repro.core.census import canonical_dyads
from repro.engine import (EngineConfig, FaultPlan, clear_plan_cache,
                          compile, list_ops, plan_cache_stats)
from repro.serve import CensusService, ServiceConfig

BACKENDS = ["xla", "pallas", "distributed"]
ALL_OPS = tuple(list_ops())
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def _graph(seed=0, n=48, m=300):
    rng = np.random.default_rng(seed)
    return from_edges(n, rng.integers(0, n, m), rng.integers(0, n, m))


# ----------------------------------------------------------------------------
# host-side layout: cuts, owned dyads, local CSR
# ----------------------------------------------------------------------------

def test_partition_cuts_cover_and_balance():
    g = _graph(3)
    for parts in (1, 2, 4, 8):
        cuts = partition_cuts(g, parts)
        assert cuts[0] == 0 and cuts[-1] == g.n
        assert (np.diff(cuts) >= 0).all()
        assert len(cuts) == parts + 1
        total = sum(len(shard_dyads(g, int(a), int(b))[0])
                    for a, b in zip(cuts[:-1], cuts[1:]))
        assert total == g.n_dyads


def test_shard_dyads_concat_is_canonical_stream():
    g = _graph(4)
    cuts = partition_cuts(g, 4)
    us, vs = zip(*(shard_dyads(g, int(a), int(b))
                   for a, b in zip(cuts[:-1], cuts[1:])))
    u, v = np.concatenate(us), np.concatenate(vs)
    cu, cv = canonical_dyads(g)
    assert np.array_equal(u, cu) and np.array_equal(v, cv)


def test_local_arrays_keep_rows_bit_identical():
    g = _graph(5)
    part = partition_graph(g, 4)
    out_ptr = np.asarray(g.arrays.out_ptr)
    out_idx = np.asarray(g.arrays.out_idx)
    for s in part.shards:
        local = build_local_arrays(g, s.lo, s.hi, s.halo)
        kept = np.union1d(np.arange(s.lo, s.hi), s.halo).astype(int)
        for w in kept:
            row = out_idx[out_ptr[w]:out_ptr[w + 1]]
            lrow = local.out_idx[local.out_ptr[w]:local.out_ptr[w + 1]]
            assert np.array_equal(row, lrow), (s.index, w)
        # non-kept rows are empty — probes of them always miss
        absent = np.setdiff1d(np.arange(g.n), kept)
        assert (local.out_ptr[absent + 1] == local.out_ptr[absent]).all()
        assert int(local.out_ptr[-1]) == s.m_out
        assert int(local.nbr_ptr[-1]) == s.m_nbr


@pytest.mark.parametrize("seed", [5, 6])
def test_local_arrays_keep_kept_rows_codes_bit_identical(seed):
    """The direction-coded rows of every kept vertex are the global
    ones, entry for entry, beside the same neighbour ids."""
    g = _graph(seed)
    part = partition_graph(g, 4)
    nbr_ptr = np.asarray(g.arrays.nbr_ptr)
    nbr_code = np.asarray(g.arrays.nbr_code)
    for s in part.shards:
        local = build_local_arrays(g, s.lo, s.hi, s.halo)
        assert local.nbr_code.shape == local.nbr_idx.shape
        assert np.array_equal(local.nbr_code >> 2, local.nbr_idx)
        kept = np.union1d(np.arange(s.lo, s.hi), s.halo).astype(int)
        for w in kept:
            row = nbr_code[nbr_ptr[w]:nbr_ptr[w + 1]]
            lrow = local.nbr_code[local.nbr_ptr[w]:local.nbr_ptr[w + 1]]
            assert np.array_equal(row, lrow), (s.index, w)


def test_star_graph_hub_row_is_every_remote_shards_halo():
    # hub 0 with spokes 1..n-1: every dyad involves the hub, so every
    # shard that doesn't own vertex 0 must carry its row as halo.
    n = 33
    spokes = np.arange(1, n)
    g = from_edges(n, np.zeros(n - 1, dtype=int), spokes)
    part = partition_graph(g, 4)
    for s in part.shards:
        if s.n_dyads and not (s.lo <= 0 < s.hi):
            assert 0 in s.halo, s
    base = compile(g, ALL_OPS, EngineConfig(backend="xla")).run_raw(g)
    plan = compile(g, ALL_OPS, EngineConfig(backend="xla", partitions=4))
    assert np.array_equal(plan.run_raw(g), base)


# ----------------------------------------------------------------------------
# bit-identity: partitions × backend × schedule, one sync pinned
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("schedule", ["static", "dynamic"])
def test_partitioned_bit_identity_every_op(backend, schedule):
    g = _graph(7, n=40, m=240)
    want = brute_force_census(g).counts
    base = compile(g, ALL_OPS, EngineConfig(backend="xla")).run_raw(g)
    for parts in (1, 2, 4, 8):
        cfg = EngineConfig(backend=backend, schedule=schedule,
                           partitions=parts, batch=64, chunk_dyads=64)
        plan = compile(g, ALL_OPS, cfg)
        s0 = plan.stats["host_syncs"]
        raw = plan.run_raw(g)
        # regression pin: a partitioned run is still ONE device→host sync
        assert plan.stats["host_syncs"] - s0 == 1, (backend, parts)
        assert np.array_equal(raw, base), (backend, schedule, parts)
        res = plan.run(g)
        assert (res["triad_census"].counts == want).all()
        if parts > 1:
            ps = plan.stats["partition"]
            assert ps["partitions"] == min(parts, g.n)
            assert sum(ps["shard_dyads"]) == g.n_dyads
            assert len(ps["halo_sizes"]) == ps["partitions"]


def test_partitioned_spill_bit_identity():
    g = _graph(9)
    base = compile(g, ALL_OPS, EngineConfig(backend="xla")).run_raw(g)
    for backend in BACKENDS:
        cfg = EngineConfig(backend=backend, partitions=4, spill=True)
        plan = compile(g, ALL_OPS, cfg)
        assert np.array_equal(plan.run_raw(g), base), backend
        assert plan.stats["partition"]["spill"] is True


def test_partitioned_empty_and_tiny_graphs():
    empty = from_edges(5, np.array([], int), np.array([], int))
    single = from_edges(4, np.array([0]), np.array([1]))
    for g in (empty, single):
        base = compile(g, ALL_OPS, EngineConfig(backend="xla")).run_raw(g)
        plan = compile(g, ALL_OPS, EngineConfig(backend="xla", partitions=8))
        assert np.array_equal(plan.run_raw(g), base)


def test_run_batch_partitioned_falls_back_memberwise():
    gs = [_graph(s, n=32, m=160) for s in range(3)]
    base = compile(gs[0], ALL_OPS, EngineConfig(backend="xla"))
    plan = compile(gs[0], ALL_OPS, EngineConfig(backend="xla", partitions=2))
    outs = plan.run_batch(gs)
    for g, out in zip(gs, outs):
        want = base.run(g)
        assert (out["triad_census"].counts
                == want["triad_census"].counts).all()


# ----------------------------------------------------------------------------
# out-of-core: mmap graph + spilled dyad staging under a budget
# ----------------------------------------------------------------------------

def test_mmap_graph_matches_device_graph(tmp_path):
    rng = np.random.default_rng(11)
    n, m = 64, 500
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    g = from_edges(n, src, dst)
    gm = from_edges_mmap(n, src, dst, dir=str(tmp_path))
    assert (gm.n, gm.m, gm.m_nbr) == (g.n, g.m, g.m_nbr)
    assert isinstance(gm.arrays.nbr_idx, np.ndarray)  # host-resident
    for a, b in zip(g.arrays[:5], gm.arrays[:5]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    s1, d1 = arcs_host(g)
    s2 = np.concatenate([s for s, _ in arcs_host_iter(gm, block=13)])
    d2 = np.concatenate([d for _, d in arcs_host_iter(gm, block=13)])
    assert np.array_equal(s1, s2) and np.array_equal(d1, d2)
    cuts = partition_cuts(gm, 4)
    s3 = np.concatenate([s for s, _ in arcs_host_iter(gm, cuts=cuts)])
    assert np.array_equal(s1, s3)


def test_spill_run_completes_under_capped_staging_budget(tmp_path):
    # a dyad stream whose total staging exceeds an artificial budget:
    # the per-shard staging peak must stay under the cap while the full
    # stream (which a single-device run would materialize) exceeds it.
    g = generators.rmat(9, edge_factor=8, seed=2)  # n=512, ~4k arcs
    gm = from_edges_mmap(g.n, *arcs_host(g))
    base = compile(g, ("triad_census",),
                   EngineConfig(backend="xla")).run_raw(g)
    cfg = EngineConfig(backend="xla", partitions=8, spill=str(tmp_path),
                       batch=32, chunk_dyads=32)
    plan = compile(gm, ("triad_census",), cfg)
    raw = plan.run_raw(gm)
    assert np.array_equal(raw, base)
    ps = plan.stats["partition"]
    cap = ps["stream_bytes"] // 2  # the artificial in-memory budget
    assert ps["max_stage_bytes"] <= cap < ps["stream_bytes"], ps
    assert not os.listdir(str(tmp_path))  # scratch removed after the run


# ----------------------------------------------------------------------------
# cross composition: delta × fault recovery × reorder on partitioned plans
# ----------------------------------------------------------------------------

def test_partition_delta_touches_only_owner_shards():
    g = _graph(13, n=64, m=380)
    plan = compile(g, ALL_OPS, EngineConfig(backend="xla", partitions=8,
                                            delta_threshold=1.0))
    raw = plan.run_raw(g)
    delta = GraphDelta(edges_added=np.array([[1, 2]]))
    s0 = plan.stats["host_syncs"]
    res = plan.apply_delta(g, delta, raw)
    assert res.mode == "delta"
    assert plan.stats["host_syncs"] - s0 == 1  # the correction's one sync
    touched = plan.stats["partition"]["delta_shards"]
    assert 1 <= touched < plan.partitions
    want = compile(res.graph, ALL_OPS,
                   EngineConfig(backend="xla")).run_raw(res.graph)
    assert np.array_equal(res.raw, want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_partition_delta_stream_matches_full(backend):
    g = _graph(17, n=40, m=220)
    cfg = EngineConfig(backend=backend, partitions=4, delta_threshold=1.0)
    plan = compile(g, ALL_OPS, cfg)
    raw = plan.run_raw(g)
    rng = np.random.default_rng(5)
    for step in range(3):
        delta = GraphDelta(
            edges_added=rng.integers(0, g.n, (3, 2)),
            edges_removed=rng.integers(0, g.n, (2, 2)))
        res = plan.apply_delta(g, delta, raw)
        g, raw = res.graph, res.raw
        want = compile(g, ALL_OPS, EngineConfig(backend="xla")).run_raw(g)
        assert np.array_equal(raw, want), (backend, step, res.mode)


def test_partition_fault_recovery_bit_identical():
    g = _graph(19)
    base = compile(g, ALL_OPS, EngineConfig(backend="xla")).run_raw(g)
    fp = FaultPlan(seed=7, chunk_failure_rate=0.3, fail_attempts=1)
    for schedule in ("static", "dynamic"):
        cfg = EngineConfig(backend="xla", partitions=4, schedule=schedule,
                           batch=32, chunk_dyads=32, fault_plan=fp)
        plan = compile(g, ALL_OPS, cfg)
        s0 = plan.stats["host_syncs"]
        raw = plan.run_raw(g)
        assert np.array_equal(raw, base), schedule
        assert plan.stats["host_syncs"] - s0 == 1
        assert plan.stats["faults"]["retries"] > 0  # faults actually fired


def test_partition_runtime_fault_demotes_whole_partitioned_run():
    g = _graph(21)
    base = compile(g, ALL_OPS, EngineConfig(backend="xla")).run_raw(g)
    fp = FaultPlan(seed=3, runtime_failure=("pallas",))
    plan = compile(g, ALL_OPS, EngineConfig(backend="pallas", partitions=4,
                                            fault_plan=fp))
    raw = plan.run_raw(g)
    assert np.array_equal(raw, base)
    assert plan.backend == "xla"  # the ladder demoted the partitioned run
    assert plan.degradation and plan.degradation[0]["rung"] == "pallas->xla"


def test_partition_composes_with_reorder():
    g = _graph(23)
    base = compile(g, ALL_OPS, EngineConfig(backend="xla")).run_raw(g)
    for reorder in ("degree", "bfs", "rcm"):
        cfg = EngineConfig(backend="xla", partitions=4, reorder=reorder)
        plan = compile(g, ALL_OPS, cfg)
        assert np.array_equal(plan.run_raw(g), base), reorder


# ----------------------------------------------------------------------------
# config validation, locality guard, metadata surfacing
# ----------------------------------------------------------------------------

def test_partition_config_validation_messages():
    with pytest.raises(ValueError, match="partitions must be an int >= 1"):
        EngineConfig(partitions=0)
    with pytest.raises(ValueError, match="partitions must be an int >= 1"):
        EngineConfig(partitions=2.5)
    with pytest.raises(ValueError, match="spill must be None, a bool"):
        EngineConfig(spill=3)
    with pytest.raises(ValueError, match="device-resident path"):
        EngineConfig(partitions=2, device_accum=False)
    # inert spellings normalize into the same cached plan
    g = _graph(27, n=16, m=40)
    assert compile(g, ("triad_census",), EngineConfig(partitions=None)) is \
        compile(g, ("triad_census",), EngineConfig(partitions=1, spill=False))


def test_partition_rejects_nonlocal_ops():
    from repro.engine.ops import GraphOp, register_op

    class NonLocal(GraphOp):
        name = "nonlocal_probe"
        bins = 1
        kernel_key = "triad_census"
        delta_local = False

        def finalize(self, raw, g):
            return int(raw.sum())

    register_op(NonLocal(), overwrite=True)
    g = _graph(29, n=16, m=40)
    with pytest.raises(ValueError, match="delta_local"):
        compile(g, ("nonlocal_probe",), EngineConfig(partitions=2))
    compile(g, ("nonlocal_probe",), EngineConfig(partitions=1))  # fine


def test_partition_metadata_in_plan_cache_stats():
    g = _graph(31)
    plan = compile(g, ("triad_census",),
                   EngineConfig(backend="xla", partitions=4))
    plan.run(g)
    plan.run(g)  # warm: the layout memo must hit
    entry = plan_cache_stats()["entries"][-1]
    assert entry["partitions"] == 4
    assert entry["partition_memo"] == 1
    assert sum(entry["partition"]["shard_dyads"]) == g.n_dyads
    assert len(entry["partition"]["halo_sizes"]) == 4
    unpart = compile(g, ("dyad_census",), EngineConfig(backend="xla"))
    unpart.run(g)
    entry0 = plan_cache_stats()["entries"][-1]
    assert entry0["partitions"] == 1 and "partition" not in entry0


def test_partition_metadata_in_service_stats():
    svc = CensusService(ServiceConfig(
        max_batch=2, max_wait_requests=100,
        census=EngineConfig(backend="xla", partitions=2)))
    fleet = [generators.rmat(5, edge_factor=4, seed=s) for s in range(2)]
    for g in fleet:
        svc.submit(g)
    done = svc.flush()
    assert all(c.error is None for c in done)
    st = svc.stats()
    bucket = next(iter(st["buckets"].values()))
    assert bucket["partitions"] == 2
    assert sum(bucket["partition"]["shard_dyads"]) > 0


# ----------------------------------------------------------------------------
# the sharding.rules move (seed-era sharding/partition.py is a shim)
# ----------------------------------------------------------------------------

def test_sharding_partition_shim_warns_and_reexports():
    from repro.sharding import rules
    with pytest.warns(DeprecationWarning, match="repro.sharding.rules"):
        import repro.sharding.partition as shim
        importlib.reload(shim)
    assert shim.Rules is rules.Rules
    assert shim.make_rules is rules.make_rules
    assert shim.batch_axes is rules.batch_axes
    assert shim.constrain is rules.constrain
    from repro.sharding import Rules as pkg_rules
    assert pkg_rules is rules.Rules


# ----------------------------------------------------------------------------
# the real pool: partitions=8 over 8 forced host devices in a subprocess
# ----------------------------------------------------------------------------

def test_partitioned_run_over_forced_device_pool():
    code = """
import numpy as np, jax
assert len(jax.devices()) == 8
from repro.core import brute_force_census, generators
from repro.engine import EngineConfig, compile
g = generators.rmat(7, edge_factor=4, seed=11)
want = brute_force_census(g).counts
base = compile(g, ("triad_census",), EngineConfig(backend="xla")).run_raw(g)
for backend in ("xla", "distributed"):
    cfg = EngineConfig(backend=backend, partitions=8, batch=16,
                       chunk_dyads=16, schedule="dynamic")
    plan = compile(g, ("triad_census",), cfg)
    s0 = plan.stats["host_syncs"]
    raw = plan.run_raw(g)
    assert plan.stats["host_syncs"] - s0 == 1, backend
    assert np.array_equal(raw, base), backend
    assert (plan.run(g)["triad_census"].counts == want).all()
    if backend == "xla":
        assert plan.executor.n_devices == 8
        assert len(plan.stats["device_chunks"]) > 1  # pool fanned out
print('OK')
"""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


# ----------------------------------------------------------------------------
# partition_mode: validation, cache-key normalization, explicit-mode identity
# ----------------------------------------------------------------------------

def test_partition_mode_validation_messages():
    with pytest.raises(ValueError, match=r"'serial', 'pool', 'mesh'"):
        EngineConfig(partitions=2, partition_mode="parallel")
    with pytest.raises(ValueError, match="requires partitions > 1"):
        EngineConfig(partition_mode="pool")
    with pytest.raises(ValueError, match="requires partitions > 1"):
        EngineConfig(partitions=1, partition_mode="serial")
    g = _graph(33, n=16, m=40)
    with pytest.raises(ValueError, match="mesh"):
        compile(g, ("triad_census",),
                EngineConfig(backend="xla", partitions=2,
                             partition_mode="mesh"))
    with pytest.raises(ValueError, match="pool"):
        compile(g, ("triad_census",),
                EngineConfig(backend="distributed", partitions=2,
                             partition_mode="pool"))


def test_partition_mode_cache_key_normalization():
    g = _graph(35, n=16, m=40)
    # None resolves to the backend default and shares its plan entry
    default = compile(g, ("triad_census",),
                      EngineConfig(backend="xla", partitions=2))
    explicit = compile(g, ("triad_census",),
                       EngineConfig(backend="xla", partitions=2,
                                    partition_mode="pool"))
    assert default is explicit
    assert default.partition_mode == "pool"
    # a different mode is a different plan
    serial = compile(g, ("triad_census",),
                     EngineConfig(backend="xla", partitions=2,
                                  partition_mode="serial"))
    assert serial is not default
    assert serial.partition_mode == "serial"
    # spill defaults the mode to serial (one resident shard at a time)
    spilled = compile(g, ("triad_census",),
                      EngineConfig(backend="xla", partitions=2, spill=True))
    assert spilled.partition_mode == "serial"
    entry = plan_cache_stats()["entries"][-1]
    assert entry["partition_mode"] == "serial"


@pytest.mark.parametrize("backend", BACKENDS)
def test_partition_mode_explicit_bit_identity(backend):
    g = _graph(37, n=40, m=260)
    base = compile(g, ALL_OPS, EngineConfig(backend=backend)).run_raw(g)
    modes = (("mesh", "serial") if backend == "distributed"
             else ("pool", "serial"))
    for mode in modes:
        plan = compile(g, ALL_OPS,
                       EngineConfig(backend=backend, partitions=4,
                                    partition_mode=mode))
        s0 = plan.stats["host_syncs"]
        raw = plan.run_raw(g)
        assert np.array_equal(raw, base), (backend, mode)
        assert plan.stats["host_syncs"] - s0 == 1
        ps = plan.stats["partition"]
        assert ps["mode"] == mode


def test_partition_staging_hoisted_once_per_shard():
    # satellite regression: context staging happens exactly ONCE per
    # non-empty shard — never per chunk, never per worker — on both the
    # serial rung and the (single-device degenerate) pool.
    g = _graph(39, n=48, m=300)
    for mode in ("serial", "pool"):
        plan = compile(g, ("triad_census",),
                       EngineConfig(backend="xla", partitions=4,
                                    chunk_dyads=16, partition_mode=mode))
        plan.run(g)
        ps = plan.stats["partition"]
        nonempty = sum(1 for d in ps["shard_dyads"] if d)
        assert ps["h2d_puts"] == nonempty, (mode, ps["h2d_puts"], nonempty)
        assert set(ps["shard_times"]) == {
            s for s, d in enumerate(ps["shard_dyads"]) if d}
        for t in ps["shard_times"].values():
            assert t["end"] >= t["start"] and t["tasks"] >= 1
        assert 0.0 <= ps["shard_overlap"] <= 1.0
        # chunks dispatched == chunks folded, per device
        assert (sum(plan.stats["device_chunks"].values())
                == plan.stats["chunks"])


def test_partition_observables_in_plan_cache_stats():
    g = _graph(41)
    plan = compile(g, ("triad_census",),
                   EngineConfig(backend="xla", partitions=4))
    plan.run(g)
    entry = plan_cache_stats()["entries"][-1]
    ps = entry["partition"]
    assert ps["mode"] == entry["partition_mode"]
    for key in ("h2d_puts", "d2d_puts", "max_shard_bytes",
                "shard_overlap", "shard_times"):
        assert key in ps, key
    from repro.engine.partition import full_context_bytes
    # pow2 bucket rounding can equalize them on tiny graphs; the strict
    # ~P-fold drop is pinned by the benchmark on a locality-rich graph.
    assert 0 < ps["max_shard_bytes"] <= full_context_bytes(plan)


# ----------------------------------------------------------------------------
# device-side halo exchange: routing metadata + assembled-array identity
# ----------------------------------------------------------------------------

def test_halo_by_owner_groups_are_owner_contiguous():
    from repro.core.partition import halo_by_owner
    g = _graph(43, n=64, m=400)
    part = partition_graph(g, 4)
    for shard in part.shards:
        groups = halo_by_owner(part.cuts, shard.halo)
        rebuilt = np.concatenate([ids for _, ids in groups]) if groups \
            else np.empty(0, dtype=np.int64)
        assert np.array_equal(rebuilt, shard.halo)  # nothing lost/reordered
        owners = [o for o, _ in groups]
        assert owners == sorted(set(owners))  # one contiguous run per owner
        for o, ids in groups:
            assert o != shard.index  # halo rows are remote by construction
            lo, hi = int(part.cuts[o]), int(part.cuts[o + 1])
            assert ((ids >= lo) & (ids < hi)).all()


def test_pool_staging_assembles_exact_local_arrays():
    # the pool path's device-assembled shard context (ptr staging + owned
    # block scatter + per-owner halo exchange) must equal the host-built
    # serial context BIT FOR BIT — this is what makes pool/serial/p1
    # interchangeable.
    from repro.engine.partition import (_Geometry, _exchange_halos,
                                        _finish_pool_context, _shard_arrays,
                                        _stage_pool_shard, plan_partition)
    g = _graph(45, n=64, m=400)
    for backend in ("xla", "pallas"):
        plan = compile(g, ("triad_census",),
                       EngineConfig(backend=backend, partitions=4,
                                    partition_mode="pool"))
        part = plan_partition(plan, g)
        geom = _Geometry(plan, part)
        dev = plan.executor.devices[0]
        pstats = {"d2d_puts": 0}
        work = {}
        for shard in part.shards:
            if shard.n_dyads == 0:
                continue
            u, v = shard_dyads(g, shard.lo, shard.hi)
            work[shard.index] = _stage_pool_shard(plan, g, shard, geom,
                                                  u, v, dev)
        _exchange_halos(plan, g, part, work, pstats)
        for s, w in work.items():
            arrays, _n, _du, _dv = _finish_pool_context(plan, w)
            want = _shard_arrays(plan, g, part.shards[s], geom)
            for field in GraphArrays._fields:
                a, b = getattr(arrays, field), getattr(want, field)
                assert np.array_equal(np.asarray(a), np.asarray(b)), \
                    (backend, s, field)


# ----------------------------------------------------------------------------
# concurrent pool over 8 forced host devices (subprocess)
# ----------------------------------------------------------------------------

def test_concurrent_pool_over_forced_device_pool():
    code = """
import numpy as np, jax
assert len(jax.devices()) == 8
from repro.core import brute_force_census, generators
from repro.engine import EngineConfig, FaultPlan, compile
g = generators.rmat(7, edge_factor=4, seed=11)
want = brute_force_census(g).counts
base = compile(g, ("triad_census",), EngineConfig(backend="xla")).run_raw(g)
# concurrent residency: every shard staged once, halos exchanged
# device-to-device, >= 2 shards in flight at once, one sync.
plan = compile(g, ("triad_census",),
               EngineConfig(backend="xla", partitions=8, batch=16,
                            chunk_dyads=16, schedule="dynamic"))
assert plan.partition_mode == "pool"
s0 = plan.stats["host_syncs"]
raw = plan.run_raw(g)
assert plan.stats["host_syncs"] - s0 == 1
assert np.array_equal(raw, base)
ps = plan.stats["partition"]
nonempty = sum(1 for d in ps["shard_dyads"] if d)
assert ps["mode"] == "pool"
assert ps["h2d_puts"] == nonempty, ps
assert ps["d2d_puts"] > 0, ps
assert ps["shard_overlap"] > 0.0, ps
assert len(plan.stats["device_chunks"]) > 1
assert sum(plan.stats["device_chunks"].values()) == plan.stats["chunks"]
# device loss mid-run: the dead home's shards re-home onto survivors,
# their contexts re-stage, and the result stays bit-identical in one
# sync.  The loss is a thread race (the dead worker must win a task),
# so re-run the warm plan until it lands.
lossy = compile(g, ("triad_census",),
                EngineConfig(backend="xla", partitions=8, batch=16,
                             chunk_dyads=16, schedule="dynamic",
                             fault_plan=FaultPlan(seed=5,
                                                  device_loss=(3,))))
runs = 0
for _ in range(8):
    raw = lossy.run_raw(g)
    runs += 1
    assert np.array_equal(raw, base)
    if lossy.stats["faults"]["device_losses"]:
        break
fs = lossy.stats["faults"]
assert fs["device_losses"] >= 1 and fs["quarantines"] >= 1, fs
assert lossy.stats["partition"].get("rehomes", 0) >= 1
assert lossy.stats["host_syncs"] == runs
print('OK')
"""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC}
    env.pop("REPRO_FAULT_PLAN", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
