"""The benchmark's generators: deterministic from their seeds, the same
graph as the program's R-MAT generator, and the shapes the cells rely on."""
import numpy as np
import pytest

from benchlib import generators


def undirected_max_degree(n, src, dst):
    und = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    return int(np.bincount(und // n, minlength=n).max())


def test_kronecker_is_the_programs_rmat():
    from repro.core.generators import rmat
    from repro.core.graph import arcs_host
    n, src, dst = generators.kronecker(9, 16, seed=4)
    s, d = arcs_host(rmat(9, 16, seed=4))
    assert n == 512
    assert src.tolist() == s.tolist() and dst.tolist() == d.tolist()


@pytest.mark.parametrize("seed", range(12))
def test_scale_13_keeps_the_4096_wide_top_tile(seed):
    n, src, dst = generators.kronecker(13, 16, seed=seed)
    k = undirected_max_degree(n, src, dst)
    assert 2048 < k <= 4096
    assert 105_000 < len(src) < 115_000


@pytest.mark.parametrize("scale,graph_seed", [(8, 0), (9, 3)])
def test_make_graph_is_deterministic_and_relabels_only(scale, graph_seed):
    from benchlib import reference
    spec = {"kind": "kronecker", "scale": scale, "edge_factor": 16,
            "seed": graph_seed}

    def census(g):
        return reference.triad_census(g["n"], g["src"], g["dst"]).tolist()

    big = 2 ** 31 + 1234
    a = generators.make_graph(spec, big)
    b = generators.make_graph(spec, big)
    c = generators.make_graph(spec, 7)
    assert a["src"].tolist() == b["src"].tolist()
    assert a["src"].tolist() != c["src"].tolist()
    # another seed is the same graph under other vertex ids
    assert census(a) == census(c)
    assert len(a["src"]) == len(c["src"])


def test_an_unknown_graph_kind_is_an_error():
    with pytest.raises(ValueError):
        generators.make_graph({"kind": "no_such_generator"}, 1)
