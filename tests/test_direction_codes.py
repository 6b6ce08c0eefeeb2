"""Direction-coded neighbour rows (``GraphArrays.nbr_code``): each entry
``4·w + dir`` of ``N(x)`` says whether ``x -> w`` (bit 0) and ``w -> x``
(bit 1), checked against plain arc sets; every field of the host CSR
build against one made from Python sets and sorted lists; and the Pallas
backend, which packs ids into these codes, never runs a graph whose ids
do not fit."""
from collections import defaultdict

import numpy as np
import pytest

from repro.core.delta import GraphDelta, apply_delta_csr
from repro.core.graph import (MAX_CODED_VERTICES, GraphArrays,
                              _build_host_arrays, arcs_host, from_edges,
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


def _plain_build(n, src, dst, directed=True):
    """``_build_host_arrays``'s fields and metadata from Python sets: the
    distinct non-loop arcs, each row's sorted out- and open neighbours,
    and each neighbour's direction bits."""
    arcs = {(int(a), int(b)) for a, b in zip(src, dst) if a != b}
    if not directed:
        arcs |= {(b, a) for a, b in arcs}
    out, nbr = defaultdict(set), defaultdict(set)
    for a, b in arcs:
        out[a].add(b)
        nbr[a].add(b)
        nbr[b].add(a)
    out_rows = [sorted(out[x]) for x in range(n)]
    nbr_rows = [sorted(nbr[x]) for x in range(n)]
    codes = [4 * w + ((x, w) in arcs) + 2 * ((w, x) in arcs)
             for x in range(n) for w in nbr_rows[x]]
    out_deg = [len(r) for r in out_rows]
    nbr_deg = [len(r) for r in nbr_rows]
    fields = {"out_ptr": np.cumsum([0] + out_deg),
              "out_idx": [w for r in out_rows for w in r],
              "nbr_ptr": np.cumsum([0] + nbr_deg),
              "nbr_idx": [w for r in nbr_rows for w in r],
              "nbr_deg": nbr_deg, "nbr_code": codes}
    return fields, (len(arcs), len(codes), max(nbr_deg, default=0),
                    max(out_deg, default=0))


def _kronecker_arcs(scale, seed, edge_factor=16):
    """Raw Graph500 Kronecker draws (A=0.57, B=C=0.19), ids permuted:
    repeats and self-loops left in."""
    rng = np.random.default_rng(seed)
    m = edge_factor << scale
    src, dst = np.zeros(m, np.int64), np.zeros(m, np.int64)
    for bit in range(scale):
        r = rng.random(m)
        src |= (r >= 0.76).astype(np.int64) << bit
        dst |= (((r >= 0.57) & (r < 0.76)) | (r >= 0.95)).astype(
            np.int64) << bit
    perm = rng.permutation(1 << scale)
    return 1 << scale, perm[src], perm[dst]


def _build_case(case):
    """``(n, src, dst, directed)`` of one input class of the build."""
    rng = np.random.default_rng(11)
    n = 30
    src, dst = rng.integers(0, n, 120), rng.integers(0, n, 120)
    distinct = np.unique(src * n + dst)
    distinct = distinct[distinct // n != distinct % n]
    u, v = distinct // n, distinct % n
    if case == "sorted":  # distinct arcs in (src, dst) order
        return n, u, v, True
    if case == "unsorted":
        p = rng.permutation(u.size)
        return n, u[p], v[p], True
    if case == "duplicates":
        return n, np.concatenate([v, v[::2], v]), np.concatenate(
            [u, u[::2], u]), True
    if case == "self_loops":
        loops = np.array([0, 7, 7, 29])
        return n, np.concatenate([loops, u]), np.concatenate([loops, v]), True
    if case == "mutual":
        half = u.size // 2
        return n, np.concatenate([u, v[:half]]), np.concatenate(
            [v, u[:half]]), True
    if case == "isolated":  # vertices 30..59 touch no arc
        return 2 * n, u, v, True
    if case == "empty":
        return 5, np.zeros(0, int), np.zeros(0, int), True
    if case == "one_vertex":
        return 1, np.array([0]), np.array([0]), True
    if case == "undirected":
        return n, src, dst, False
    if case == "kronecker_s8":
        return (*_kronecker_arcs(8, seed=5), True)
    raise ValueError(case)


def _host_fields(arrays):
    return {f: np.asarray(getattr(arrays, f)) for f in GraphArrays._fields}


def _assert_build_equals(got, meta, want):
    want_fields, want_meta = want
    assert meta == want_meta
    for f in GraphArrays._fields:
        assert got[f].dtype == np.int32, f
        assert np.array_equal(got[f], np.asarray(want_fields[f])), f


@pytest.mark.parametrize("case", [
    "sorted", "unsorted", "duplicates", "self_loops", "mutual", "isolated",
    "empty", "one_vertex", "undirected", "kronecker_s8", "delta"])
def test_host_build_matches_plain_build(case):
    """Every field and count of the host CSR build, for each class of arc
    list it is handed, against :func:`_plain_build`.  ``delta``: a graph
    mutated by :func:`apply_delta_csr` (unsorted arcs appended to a CSR's
    arc list) equals both that build and ``from_edges`` of the mutated arc
    list, field for field."""
    if case != "delta":
        n, src, dst, directed = _build_case(case)
        host, *meta = _build_host_arrays(n, src, dst, directed=directed)
        _assert_build_equals(_host_fields(host), tuple(meta),
                             _plain_build(n, src, dst, directed))
        return
    n, src, dst, _ = _build_case("mutual")
    g = from_edges(n, src, dst)
    s, d = arcs_host(g)
    rng = np.random.default_rng(3)
    drop = rng.choice(s.size, 8, replace=False)
    add = np.array([[29, 0], [0, 29], [4, 17], [17, 4], [12, 13], [2, 5],
                    [s[0], d[0]], [d[1], s[1]]])
    g2 = apply_delta_csr(g, GraphDelta(
        edges_added=add, edges_removed=np.stack([s[drop], d[drop]], 1)))
    keep = np.ones(s.size, bool)
    keep[drop] = False
    src2 = np.concatenate([s[keep], add[:, 0]])
    dst2 = np.concatenate([d[keep], add[:, 1]])
    want = _plain_build(n, src2, dst2)
    meta = (g2.m, g2.m_nbr, g2.max_deg, g2.max_out_deg)
    _assert_build_equals(_host_fields(g2.arrays), meta, want)
    rebuilt = from_edges(n, src2, dst2)
    _assert_build_equals(
        _host_fields(rebuilt.arrays),
        (rebuilt.m, rebuilt.m_nbr, rebuilt.max_deg, rebuilt.max_out_deg),
        want)


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
