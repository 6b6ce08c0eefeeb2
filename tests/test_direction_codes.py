"""Direction-coded neighbour rows (``GraphArrays.nbr_code``): each entry
``4·w + dir`` of ``N(x)`` says whether ``x -> w`` (bit 0) and ``w -> x``
(bit 1), checked against plain arc sets; and the Pallas backend, which
packs ids into these codes, never runs a graph whose ids do not fit."""
import numpy as np
import pytest

from repro.core.graph import (MAX_CODED_VERTICES, from_edges,
                              from_edges_mmap)
from repro.engine import EngineConfig, clear_plan_cache, compile
from repro.engine.plan import GraphMeta
from repro.kernels.triad_census import SENTINEL


def _arcs(case):
    rng = np.random.default_rng(7)
    if case == "empty":
        return 5, np.zeros(0, int), np.zeros(0, int)
    n = 40 if case == "isolated" else 25
    hi = 20  # in "isolated", vertices 20..39 touch no arc
    src, dst = rng.integers(0, hi, 150), rng.integers(0, hi, 150)
    # a third of them mutual, plus self-loops and duplicates to drop
    src = np.concatenate([src, dst[:50], [3, 3], src[:10]])
    dst = np.concatenate([dst, src[:50], [3, 3], dst[:10]])
    return n, src, dst


def _expected(n, src, dst):
    """Row x: sorted neighbours w with (x -> w) + 2·(w -> x), from sets."""
    arcs = {(int(a), int(b)) for a, b in zip(src, dst) if a != b}
    rows = []
    for x in range(n):
        nbrs = sorted({b for a, b in arcs if a == x}
                      | {a for a, b in arcs if b == x})
        rows.append([4 * w + ((x, w) in arcs) + 2 * ((w, x) in arcs)
                     for w in nbrs])
    return rows


@pytest.mark.parametrize("case", ["random", "isolated", "empty"])
@pytest.mark.parametrize("build", [from_edges, from_edges_mmap])
def test_nbr_code_matches_arc_sets(case, build, tmp_path):
    n, src, dst = _arcs(case)
    kw = {"dir": str(tmp_path)} if build is from_edges_mmap else {}
    g = build(n, src, dst, **kw)
    ptr = np.asarray(g.arrays.nbr_ptr)
    code = np.asarray(g.arrays.nbr_code)
    assert code.dtype == np.int32 and code.shape == (g.m_nbr,)
    assert np.array_equal(code >> 2, np.asarray(g.arrays.nbr_idx))
    want = _expected(n, src, dst)
    for x in range(n):
        assert list(code[ptr[x]:ptr[x + 1]]) == want[x], x
    if case != "empty":
        dirs = code & 3
        assert set(dirs) == {1, 2, 3}  # out only, in only, mutual
        assert (code < SENTINEL).all()


def test_undirected_edges_are_coded_mutual():
    g = from_edges(6, np.array([0, 1, 2]), np.array([1, 2, 5]),
                   directed=False)
    assert (np.asarray(g.arrays.nbr_code) & 3 == 3).all()


@pytest.mark.parametrize("fallback", [True, False])
def test_pallas_never_runs_a_graph_whose_ids_overflow_the_codes(fallback):
    """A plan whose vertex bucket passes MAX_CODED_VERTICES is demoted to
    xla with the reason recorded, or refused with a clear error when
    demotion is off — decided from the metadata, with no such graph
    built.  At the limit itself the largest code still sits below
    SENTINEL, so that plan stays on pallas."""
    assert 4 * (MAX_CODED_VERTICES - 1) + 3 < SENTINEL
    clear_plan_cache()
    try:
        def meta(n_bucket):
            return GraphMeta(n_bucket=n_bucket, k=32, member_iters=6,
                             m_out_bucket=256, m_nbr_bucket=512)

        config = EngineConfig(backend="pallas", backend_fallback=fallback)
        assert compile(meta(MAX_CODED_VERTICES),
                       config=config).backend == "pallas"
        big = meta(2 * MAX_CODED_VERTICES)
        if not fallback:
            with pytest.raises(ValueError, match="nbr_code"):
                compile(big, config=config)
            return
        with pytest.warns(RuntimeWarning, match="demoted to xla"):
            plan = compile(big, config=config)
        assert plan.backend == "xla" and plan.requested_backend == "pallas"
        (event,) = plan.degradation
        assert event["stage"] == "compile" and "nbr_code" in event["reason"]
    finally:
        clear_plan_cache()
