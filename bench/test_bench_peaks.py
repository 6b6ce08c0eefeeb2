"""Peaks table and the census's necessary bytes."""
import ast
import os

import numpy as np
import pytest

from benchlib import generators, peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def test_peaks_known_kind_and_unknown_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_census_bytes_pinned_on_a_small_graph():
    # arcs 0->1, 1->0, 1->2: rows (out + in + undirected) are 3, 5, 2 long;
    # connected pairs (0,1) and (1,2) read 3+5 and 5+2 entries of 4 bytes.
    assert peaks.census_bytes(3, [0, 1, 1], [1, 0, 2]) == 60
    # repeats and loops change nothing
    assert peaks.census_bytes(3, [0, 1, 1, 1, 2], [1, 0, 2, 2, 2]) == 60


def test_census_bytes_depends_on_the_graph_alone():
    n, src, dst = generators.kronecker(9, 16, seed=3)
    rng = np.random.default_rng(0)
    s, d = generators.relabel(n, src, dst, rng)
    assert peaks.census_bytes(n, src, dst) == peaks.census_bytes(n, s, d)


@pytest.mark.parametrize("module", ["peaks", "reference", "generators",
                                    "trace"])
def test_yardstick_imports_nothing_of_the_program(module):
    path = os.path.join(HERE, "benchlib", module + ".py")
    tree = ast.parse(open(path).read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module or "" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert not any(x.startswith("repro") for x in names)
