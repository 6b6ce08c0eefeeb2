"""The plain reference census against the program's brute-force oracle,
and the control (the reference without its union test) against both."""
import numpy as np
import pytest

from benchlib import generators, reference

MULTIPLICITY = (1, 6, 3, 3, 3, 6, 6, 6, 6, 2, 3, 3, 3, 6, 6, 1)


def brute(n, src, dst):
    from repro.core import brute_force_census
    from repro.core.graph import from_edges
    return brute_force_census(from_edges(n, src, dst)).counts


def test_code_table_class_sizes():
    assert tuple(np.bincount(reference.TABLE, minlength=16)) == MULTIPLICITY


@pytest.mark.parametrize("scale,edge_factor,seed", [
    (5, 4, 0), (6, 8, 1), (7, 16, 2), (8, 4, 2 ** 31 + 11)])
def test_reference_equals_brute_force_on_kronecker(scale, edge_factor, seed):
    n, src, dst = generators.kronecker(scale, edge_factor, seed=seed)
    want = brute(n, src, dst)
    assert reference.triad_census(n, src, dst).tolist() == want.tolist()
    # blocks of a few candidates each give the same counts
    got = reference.triad_census(n, src, dst, block_candidates=37)
    assert got.tolist() == want.tolist()


@pytest.mark.parametrize("seed", [0, 3])
def test_reference_equals_brute_force_after_mutations(seed):
    n, src, dst = generators.kronecker(6, 8, seed=seed)
    rng = np.random.default_rng(seed)
    arcs = set(zip(src.tolist(), dst.tolist()))
    for _ in range(3):
        gone = [sorted(arcs)[i] for i in rng.choice(len(arcs), 5,
                                                    replace=False)]
        arcs.difference_update(gone)
        new = rng.integers(0, n, size=(5, 2))
        arcs.update((int(a), int(b)) for a, b in new if a != b)
        a = np.array(sorted(arcs))
        assert (reference.triad_census(n, a[:, 0], a[:, 1]).tolist()
                == brute(n, a[:, 0], a[:, 1]).tolist())


def test_reference_on_sparse_key_path_equals_dense(monkeypatch):
    n, src, dst = generators.kronecker(7, 8, seed=5)
    dense = reference.triad_census(n, src, dst)
    monkeypatch.setattr(reference.ArcSet, "DENSE_LIMIT", 0)
    assert reference.triad_census(n, src, dst).tolist() == dense.tolist()


def test_reference_ignores_loops_and_repeats_and_empty_graph():
    n, src, dst = generators.kronecker(5, 4, seed=9)
    noisy_src = np.concatenate([src, src[:7], np.arange(5)])
    noisy_dst = np.concatenate([dst, dst[:7], np.arange(5)])
    assert (reference.triad_census(n, noisy_src, noisy_dst).tolist()
            == reference.triad_census(n, src, dst).tolist())
    empty = reference.triad_census(10, [], [])
    assert empty[0] == 120 and empty[1:].sum() == 0


def test_control_breaks_exactness():
    n, src, dst = generators.kronecker(7, 16, seed=1)
    exact = reference.triad_census(n, src, dst)
    control = reference.triad_census(n, src, dst, dedup=False)
    assert control.tolist() != exact.tolist()
