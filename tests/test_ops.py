"""GraphOp layer: per-op NumPy parity, fused == per-op bit-identity across
backends, single-pass sync counts, cache unification between the census
wrapper and the new API, config validation, registry pluggability, and
mixed-analytic serving."""
import numpy as np
import pytest

from repro.core import brute_force_census, from_edges, generators
from repro.core.graph import load_pajek_or_edgelist
from repro.engine import (CensusConfig, EngineConfig, GraphOp, clear_plan_cache,
                          compile, compile_census, get_op, list_ops,
                          plan_cache_stats, register_op)
from repro.engine.ops import unregister_op
from repro.serve import CensusService, ServiceConfig

ALL_OPS = ("triad_census", "dyad_census", "degree_stats", "triadic_profile")
BACKENDS = ["xla", "pallas", "distributed"]
CFG = EngineConfig(backend="xla", batch=16, chunk_dyads=64)


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def _assert_result_equal(got, want, ctx=""):
    """Field-exact equality for op result NamedTuples (arrays included)."""
    assert type(got) is type(want), (ctx, got, want)
    for name, a, b in zip(type(got)._fields, got, want):
        if isinstance(a, np.ndarray):
            assert np.array_equal(a, b), (ctx, name, a, b)
        else:
            assert a == b, (ctx, name, a, b)


def _pajek_graph(tmp_path):
    """A small real-format graph through the Pajek loader (directed arcs +
    undirected edges, 1-indexed)."""
    text = """*Vertices 7
1 "a"
2 "b"
3 "c"
4 "d"
5 "e"
6 "f"
7 "g"
*Arcs
1 2
2 3
3 1
4 5
5 4
*Edges
6 7
1 4
"""
    p = tmp_path / "toy.net"
    p.write_text(text)
    return load_pajek_or_edgelist(str(p))


def _graphs(tmp_path):
    rng = np.random.default_rng(3)
    n, m = 20, 60
    return [
        ("rmat", generators.rmat(6, edge_factor=4, seed=0)),
        ("random", from_edges(n, rng.integers(0, n, m),
                              rng.integers(0, n, m))),
        ("star", from_edges(9, [0] * 8, list(range(1, 9)))),
        ("tiny", from_edges(4, [0, 1], [1, 2])),
        ("empty", from_edges(5, [], [])),
        ("pajek", _pajek_graph(tmp_path)),
    ]


# ----------------------------------------------------------------------------
# per-op NumPy parity (satellite: each op validated against its reference)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("op_name", ALL_OPS)
def test_op_matches_numpy_reference(op_name, tmp_path):
    """Every built-in op reproduces its NumPy oracle on generated + real
    (Pajek-loaded) + degenerate graphs."""
    op = get_op(op_name)
    for gname, g in _graphs(tmp_path):
        got = compile(g, (op_name,), CFG).run(g)[op_name]
        _assert_result_equal(got, op.reference(g), ctx=(op_name, gname))


def test_references_are_self_consistent():
    g = generators.rmat(6, edge_factor=4, seed=1)
    dy = get_op("dyad_census").reference(g)
    assert dy.mutual + dy.asymmetric + dy.null == g.n * (g.n - 1) // 2
    assert dy.mutual + dy.asymmetric == g.n_dyads  # connected pairs
    ds = get_op("degree_stats").reference(g)
    assert ds.out_hist.sum() == ds.in_hist.sum() == g.n
    assert ds.mean_out == ds.mean_in == g.m / g.n
    tp = get_op("triadic_profile").reference(g)
    assert 0.0 <= tp.transitivity <= 1.0
    # triangles/wedges consistent with the census bins they derive from
    census = brute_force_census(g).counts
    conn = [int(nm[0]) + int(nm[1])
            for nm in __import__("repro.core.triad_table",
                                 fromlist=["TRIAD_NAMES"]).TRIAD_NAMES]
    assert tp.triangles == sum(int(c) for c, k in zip(census, conn) if k == 3)


def test_triadic_profile_known_values():
    # directed 3-cycle -> one triangle, transitivity 1
    tri = compile(from_edges(3, [0, 1, 2], [1, 2, 0]),
                  ("triadic_profile",), CFG)
    p = tri.run(from_edges(3, [0, 1, 2], [1, 2, 0]))["triadic_profile"]
    assert p == (1, 0, 1.0, 1.0)
    # path 0-1-2 -> one open wedge, no triangle
    path = from_edges(3, [0, 1], [1, 2])
    p = compile(path, ("triadic_profile",), CFG).run(path)["triadic_profile"]
    assert p.triangles == 0 and p.open_triples == 1 and p.transitivity == 0.0


# ----------------------------------------------------------------------------
# fused == per-op passes, across backends (satellite: bit-identity)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_pass_bit_identical_to_per_op_passes(backend):
    """The tentpole claim: one fused pass over the dyad stream produces
    exactly what N separate passes produce, on every backend."""
    g = generators.rmat(6, edge_factor=4, seed=2)
    cfg = EngineConfig(backend=backend, batch=16, chunk_dyads=64)
    fused = compile(g, ALL_OPS, cfg).run(g)
    assert tuple(fused) == ALL_OPS  # result dict preserves op order
    for name in ALL_OPS:
        solo = compile(g, (name,), cfg).run(g)[name]
        _assert_result_equal(solo, fused[name], ctx=(backend, name))
    assert (fused["triad_census"].counts == brute_force_census(g).counts).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_device_path_matches_sync_baseline(backend):
    g = generators.rmat(7, edge_factor=4, seed=3)
    cfg = dict(backend=backend, batch=16, chunk_dyads=64)
    dev = compile(g, ALL_OPS, EngineConfig(**cfg))
    syn = compile(g, ALL_OPS, EngineConfig(**cfg, device_accum=False))
    a, b = dev.run(g), syn.run(g)
    for name in ALL_OPS:
        _assert_result_equal(a[name], b[name], ctx=(backend, name))


def test_pallas_noncensus_plan_skips_tile_machinery():
    """A pallas plan with no census-kernel op must not pay the tile
    kernel's support system: no bucket schedule, no tiles — results
    still match the references (and, like every device path, exactly
    one sync)."""
    g = generators.rmat(6, edge_factor=4, seed=0)
    cfg = EngineConfig(backend="pallas", batch=16, chunk_dyads=64)
    plan = compile(g, ("dyad_census", "degree_stats"), cfg)
    res = plan.run(g)
    assert plan.stats["host_syncs"] == 1
    # no tile gathered, no probe column walked
    assert plan.stats["tile_slots"] == plan.stats["probe_columns"] == 0
    for name in ("dyad_census", "degree_stats"):
        _assert_result_equal(res[name], get_op(name).reference(g), ctx=name)


def test_fused_pass_single_sync_and_traversal():
    """Acceptance criterion: the 3-op fused plan costs exactly the same
    host syncs and chunk schedule as a census-only run — the extra
    analytics ride the same traversal."""
    g = generators.rmat(7, edge_factor=4, seed=1)
    solo = compile(g, ["triad_census"], EngineConfig(backend="xla",
                                                     chunk_dyads=64))
    fused = compile(g, ["triad_census", "dyad_census", "degree_stats"],
                    EngineConfig(backend="xla", chunk_dyads=64))
    solo.run(g)
    fused.run(g)
    assert fused.stats["host_syncs"] == solo.stats["host_syncs"] == 1
    assert fused.stats["chunks"] == solo.stats["chunks"] > 1


def test_fused_run_batch_bit_identical():
    """Vmapped multi-op batches == sequential multi-op runs, including a
    zero-dyad member whose results are pure closed form."""
    fleet = [generators.rmat(6, edge_factor=4, seed=s) for s in (0, 1)]
    empty = from_edges(5, [], [])
    plan = compile(fleet[0], ALL_OPS, CFG)
    s0 = plan.stats["host_syncs"]
    batched = plan.run_batch(fleet + [empty])
    assert plan.stats["host_syncs"] == s0 + 1  # one transfer for the batch
    for got, g in zip(batched, fleet + [empty]):
        want = plan.run(g)
        for name in ALL_OPS:
            _assert_result_equal(got[name], want[name], ctx=name)
    assert batched[2]["dyad_census"].null == 10
    assert batched[2]["degree_stats"].out_hist[0] == 5


def test_shared_kernel_key_single_slice():
    """triadic_profile shares the census kernel: fusing it with
    triad_census adds zero accumulator width."""
    g = generators.rmat(6, edge_factor=4, seed=0)
    both = compile(g, ("triad_census", "triadic_profile"), CFG)
    solo = compile(g, ("triad_census",), CFG)
    assert both.layout.total_bins == solo.layout.total_bins == 16


# ----------------------------------------------------------------------------
# cache unification (satellite: wrapper + new API share one entry)
# ----------------------------------------------------------------------------

def test_wrapper_and_new_api_share_one_cache_entry():
    g = generators.rmat(6, edge_factor=4, seed=0)
    wrapper = compile_census(g, CFG)
    plan = compile(g, ("triad_census",), CFG)
    st = plan_cache_stats()
    assert st["misses"] == 1 and st["hits"] == 1 and st["size"] == 1
    assert wrapper.stats is plan.stats  # same underlying compiled plan
    assert compile_census(g, CFG) is wrapper  # view identity holds
    assert (wrapper.run(g).counts
            == plan.run(g)["triad_census"].counts).all()
    entry = plan_cache_stats()["entries"][0]
    assert entry["ops"] == ("triad_census",)
    assert entry["runs"] == 2


def test_distinct_ops_are_distinct_plans():
    g = generators.rmat(6, edge_factor=4, seed=0)
    a = compile(g, ("triad_census",), CFG)
    b = compile(g, ("triad_census", "dyad_census"), CFG)
    assert a is not b and plan_cache_stats()["misses"] == 2
    # order matters for the result dict, so it is part of the key
    c = compile(g, ("dyad_census", "triad_census"), CFG)
    assert c is not b


# ----------------------------------------------------------------------------
# config validation (satellite: buckets)
# ----------------------------------------------------------------------------

def test_buckets_validated_at_construction():
    with pytest.raises(ValueError, match="non-empty"):
        EngineConfig(buckets=())
    with pytest.raises(ValueError, match="positive"):
        EngineConfig(buckets=(0, 32))
    with pytest.raises(ValueError, match="positive"):
        EngineConfig(buckets=(-4,))
    with pytest.raises(ValueError, match="strictly increasing"):
        EngineConfig(buckets=(128, 32))
    with pytest.raises(ValueError, match="strictly increasing"):
        EngineConfig(buckets=(32, 32, 128))
    # list input is normalized to a hashable tuple
    cfg = EngineConfig(buckets=[16, 64])
    assert cfg.buckets == (16, 64)
    hash(cfg)
    assert CensusConfig is EngineConfig  # the census-era alias


# ----------------------------------------------------------------------------
# registry pluggability
# ----------------------------------------------------------------------------

def test_custom_op_plugs_into_fused_pass():
    """A user-defined op registers by name and fuses with the built-ins —
    the API seam later scenarios plug into."""
    import jax.numpy as jnp

    class EdgeCountOp(GraphOp):
        """Counts connected dyads (undirected edges) from the stream."""

        name = "edge_count_test"
        bins = 1

        def make_batch_fn(self, meta, config):
            def fn(arrays, n, u, v, valid):
                return valid.sum(dtype=config.acc_jnp_dtype)[None]
            return fn

        def finalize(self, raw, g):
            return int(raw[0])

        def reference(self, g):
            return g.n_dyads

    register_op(EdgeCountOp())
    try:
        assert "edge_count_test" in list_ops()
        g = generators.rmat(6, edge_factor=4, seed=0)
        plan = compile(g, ("triad_census", "edge_count_test"), CFG)
        res = plan.run(g)
        assert res["edge_count_test"] == g.n_dyads
        assert (res["triad_census"].counts
                == brute_force_census(g).counts).all()
        with pytest.raises(ValueError, match="already registered"):
            register_op(EdgeCountOp())
    finally:
        unregister_op("edge_count_test")
    with pytest.raises(KeyError, match="edge_count_test"):
        get_op("edge_count_test")


def test_reregistered_op_gets_fresh_plan():
    """The cache keys on op instances: overwriting a registration must
    compile a fresh plan, never serve one built against the old kernel."""
    import jax.numpy as jnp

    class ConstOp(GraphOp):
        """Adds a fixed per-batch constant (distinguishes kernel vintages)."""

        name = "const_test"
        bins = 1

        def __init__(self, value):
            self.value = value

        def make_batch_fn(self, meta, config):
            val = self.value

            def fn(arrays, n, u, v, valid):
                return jnp.full((1,), val, config.acc_jnp_dtype)
            return fn

        def finalize(self, raw, g):
            return int(raw[0])

    g = generators.rmat(6, edge_factor=4, seed=0)
    register_op(ConstOp(1))
    try:
        p1 = compile(g, ("const_test",), CFG)
        v1 = p1.run(g)["const_test"]
        register_op(ConstOp(2), overwrite=True)
        p2 = compile(g, ("const_test",), CFG)
        assert p2 is not p1  # fresh plan, not the stale cached one
        assert p2.run(g)["const_test"] == 2 * v1
    finally:
        unregister_op("const_test")


def test_kernel_key_sharers_validated():
    """A rider op must match its kernel owner's bins, and the key's
    namesake owns the kernel regardless of op order."""
    g = generators.rmat(6, edge_factor=4, seed=0)

    class BadRider(GraphOp):
        """Mis-sized rider on the census kernel."""

        name = "bad_rider_test"
        kernel_key = "triad_census"
        bins = 1

    with pytest.raises(ValueError, match="bins=1 != 16"):
        compile(g, (BadRider(), "triad_census"), CFG)
    # rider listed first must not displace the namesake's kernel
    res = compile(g, ("triadic_profile", "triad_census"), CFG).run(g)
    assert (res["triad_census"].counts == brute_force_census(g).counts).all()


def test_ops_spec_validation():
    g = generators.rmat(6, edge_factor=4, seed=0)
    with pytest.raises(KeyError, match="unknown GraphOp"):
        compile(g, ("no_such_op",), CFG)
    with pytest.raises(ValueError, match="duplicate"):
        compile(g, ("dyad_census", "dyad_census"), CFG)
    with pytest.raises(ValueError, match="at least one"):
        compile(g, (), CFG)


# ----------------------------------------------------------------------------
# mixed-analytic serving
# ----------------------------------------------------------------------------

def test_service_batches_by_bucket_and_ops():
    """Same-bucket graphs with different ops form separate groups; each
    group rides one fused batch; single-op requests complete with bare
    results, multi-op requests with dicts."""
    fleet = [generators.rmat(6, edge_factor=4, seed=s) for s in range(4)]
    svc = CensusService(ServiceConfig(max_batch=2, max_wait_requests=100,
                                      census=CFG))
    r_census = svc.submit(fleet[0])                      # census-only group
    r_multi = svc.submit(fleet[1], ops=("triad_census", "degree_stats"))
    assert svc.pending == 2 and not svc.poll()           # two partial groups
    svc.submit(fleet[2])                                 # fills census group
    done = {c.request_id: c for c in svc.poll()}
    assert set(done) == {r_census, 2}
    assert done[r_census].ops == ("triad_census",)
    assert (done[r_census].result.counts
            == brute_force_census(fleet[0]).counts).all()
    svc.submit(fleet[3], ops=("triad_census", "degree_stats"))
    done = {c.request_id: c for c in svc.poll()}
    assert set(done) == {r_multi, 3}
    multi = done[r_multi]
    assert multi.ops == ("triad_census", "degree_stats")
    assert set(multi.result) == {"triad_census", "degree_stats"}
    _assert_result_equal(multi.result["degree_stats"],
                         get_op("degree_stats").reference(fleet[1]))
    st = svc.stats()
    meta = list(st["buckets"])[0]
    assert st["buckets"][meta]["by_ops"] == {
        ("triad_census",): 2, ("triad_census", "degree_stats"): 2}


def test_service_rejects_bad_ops_at_submit():
    """A bad ops spec fails the one submit, immediately — it must never
    queue and later take down its whole batch group at flush time."""
    svc = CensusService(ServiceConfig(max_batch=4, census=CFG))
    g = generators.rmat(6, edge_factor=4, seed=0)
    rid = svc.submit(g)  # a healthy pending request
    with pytest.raises(KeyError, match="unknown GraphOp"):
        svc.submit(g, ops=("dyad_censu",))  # typo
    assert svc.pending == 1  # the healthy request is untouched

    class Impostor(GraphOp):
        """Name-collides with the built-in census but is NOT registered —
        the service must refuse rather than silently run the built-in."""

        name = "triad_census"
        bins = 16

    with pytest.raises(ValueError, match="not the registered"):
        svc.submit(g, ops=(Impostor(),))
    svc.submit(g, ops=(get_op("dyad_census"),))  # registered instance: OK
    assert svc.pending == 2
    done = svc.flush()
    assert rid in [c.request_id for c in done]


def test_service_single_noncensus_op_bare_result():
    svc = CensusService(ServiceConfig(max_batch=1, census=CFG))
    g = generators.rmat(6, edge_factor=4, seed=0)
    svc.submit(g, ops="dyad_census")
    (c,) = svc.poll()
    _assert_result_equal(c.result, get_op("dyad_census").reference(g))


def test_run_fleet_with_ops():
    svc = CensusService(ServiceConfig(max_batch=4, census=CFG))
    fleet = [generators.rmat(6, edge_factor=4, seed=s) for s in range(3)]
    out = svc.run_fleet(fleet, ops=("dyad_census", "triadic_profile"))
    assert len(out) == 3
    for res, g in zip(out, fleet):
        _assert_result_equal(res["dyad_census"],
                             get_op("dyad_census").reference(g))
        _assert_result_equal(res["triadic_profile"],
                             get_op("triadic_profile").reference(g))
