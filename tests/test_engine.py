"""Engine front door: backend agreement, plan caching, streaming execution."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import brute_force_census, from_edges, generators
from repro.engine import (CensusConfig, GraphMeta, clear_plan_cache,
                          compile_census, plan_cache_stats)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


@pytest.mark.parametrize("backend", ["xla", "pallas", "distributed"])
@pytest.mark.parametrize("seed", range(3))
def test_backends_match_brute_force(backend, seed):
    g = generators.rmat(6, edge_factor=4, seed=seed)
    want = brute_force_census(g).counts
    cfg = CensusConfig(backend=backend, batch=32, chunk_dyads=256)
    got = compile_census(g, cfg).run(g).counts
    assert (got == want).all(), (backend, got, want)


@pytest.mark.parametrize("backend", ["xla", "pallas", "distributed"])
def test_backends_match_on_random_digraphs(backend):
    rng = np.random.default_rng(7)
    for trial in range(4):
        n = int(rng.integers(8, 28))
        m = int(rng.integers(n, 4 * n))
        g = from_edges(n, rng.integers(0, n, m), rng.integers(0, n, m))
        if g.n_dyads == 0:
            continue
        want = brute_force_census(g).counts
        cfg = CensusConfig(backend=backend, batch=16, chunk_dyads=64)
        got = compile_census(g, cfg).run(g).counts
        assert (got == want).all(), (backend, n, m, got, want)


def test_auto_backend_resolves_and_runs():
    g = generators.rmat(6, edge_factor=4, seed=0)
    plan = compile_census(g, CensusConfig(backend="auto"))
    assert plan.backend in ("xla", "pallas", "distributed")
    assert (plan.run(g).counts == brute_force_census(g).counts).all()


def test_plan_cache_same_shape_hits_no_retrace():
    """Second census on a same-shape graph: identical plan, zero retraces."""
    cfg = CensusConfig(backend="xla", batch=32, chunk_dyads=128)
    g1 = generators.rmat(6, edge_factor=4, seed=1)
    p1 = compile_census(g1, cfg)
    assert (p1.run(g1).counts == brute_force_census(g1).counts).all()
    traces = p1.stats["traces"]
    assert traces >= 1

    g2 = generators.rmat(6, edge_factor=4, seed=9)  # same metadata buckets
    assert GraphMeta.from_graph(g2) == GraphMeta.from_graph(g1)
    p2 = compile_census(g2, cfg)
    assert p2 is p1  # cache hit returns the identical plan object
    assert (p2.run(g2).counts == brute_force_census(g2).counts).all()
    assert p1.stats["traces"] == traces  # no retrace on the warm path
    stats = plan_cache_stats()
    assert stats["hits"] == 1 and stats["misses"] == 1


def test_plan_cache_fresh_plan_on_shape_change():
    cfg = CensusConfig(backend="xla", batch=32)
    g_small = generators.rmat(6, edge_factor=4, seed=0)
    g_big = generators.rmat(8, edge_factor=8, seed=0)
    p1 = compile_census(g_small, cfg)
    p2 = compile_census(g_big, cfg)
    assert p2 is not p1
    assert plan_cache_stats()["misses"] == 2
    # and a config change is also a fresh plan
    p3 = compile_census(g_small, CensusConfig(backend="xla", batch=64))
    assert p3 is not p1


@pytest.mark.parametrize("backend", ["xla", "pallas", "distributed"])
def test_chunked_streaming_matches_single_shot(backend):
    g = generators.rmat(7, edge_factor=4, seed=3)
    single = compile_census(
        g, CensusConfig(backend=backend, batch=16, chunk_dyads=10**6))
    chunked = compile_census(
        g, CensusConfig(backend=backend, batch=16, chunk_dyads=48))
    res_single = single.run(g)
    res_chunked = chunked.run(g)
    assert (res_single.counts == res_chunked.counts).all()
    assert chunked.stats["chunks"] > single.stats["chunks"]


def test_plan_rejects_oversized_graph():
    g_small = generators.rmat(6, edge_factor=2, seed=0)
    g_big = generators.rmat(9, edge_factor=8, seed=0)
    plan = compile_census(g_small, CensusConfig(backend="xla"))
    with pytest.raises(ValueError, match="recompile"):
        plan.run(g_big)


def test_empty_graph_closed_form_only():
    g = from_edges(5, [], [])
    plan = compile_census(g, CensusConfig(backend="xla"))
    res = plan.run(g)
    assert res.counts[0] == 5 * 4 * 3 // 6
    assert res.counts[1:].sum() == 0


def test_xla_plan_aot_lowers():
    g = generators.rmat(6, edge_factor=4, seed=0)
    plan = compile_census(g, CensusConfig(backend="xla", batch=32))
    compiled = plan.aot_lower(g).compile()
    assert compiled.cost_analysis() is not None


def test_engine_distributed_multidevice_subprocess():
    """Engine's distributed backend on a forced 8-device host mesh."""
    code = """
import numpy as np
from repro.core import brute_force_census, generators
from repro.engine import CensusConfig, compile_census
g = generators.rmat(6, edge_factor=4, seed=11)
ref = brute_force_census(g).counts
plan = compile_census(g, CensusConfig(backend="distributed", batch=16,
                                      chunk_dyads=128))
import math
assert math.prod(plan.mesh.devices.shape) == 8
got = plan.run(g).counts
assert (ref == got).all(), (ref, got)
print('OK')
"""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """An explicit JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the
    cache is the fixed <checkout>/.jax_cache."""
    import jax

    from repro.engine.config import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = use_compile_cache()
        if env_dir:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            checkout = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            assert got == os.path.join(checkout, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
