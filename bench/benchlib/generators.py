"""Graph generators of the benchmark, made from a seed on the host.

Every generator returns a directed arc list ``(n, src, dst)`` as int64
NumPy arrays: self-loops dropped, duplicate arcs removed, so the arc list
is the graph.  Nothing here imports the program under test; the harness
hands the arc lists to it.

* :func:`kronecker` — the Graph500 generator (Kronecker initiator
  A = 0.57, B = C = 0.19, D = 0.05, edge factor 16, vertex ids permuted).
  The bit loop and the permutation follow ``repro.core.generators.rmat``
  draw for draw, so a seed gives the same graph as that function.
"""
from __future__ import annotations

import numpy as np

A, B, C = 0.57, 0.19, 0.19


def _kronecker_ids(rng: np.random.Generator, scale: int, m: int,
                   a: float = A, b: float = B, c: float = C):
    """``m`` Kronecker arcs over ``2**scale`` ids, one quadrant per bit."""
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab, abc = a + b, a + b + c
    for bit in range(scale):
        r = rng.random(m)
        in_cd = r >= ab
        in_b_or_d = ((r >= a) & (r < ab)) | (r >= abc)
        src |= in_cd.astype(np.int64) << bit
        dst |= in_b_or_d.astype(np.int64) << bit
    return src, dst


def dedup_arcs(n: int, src, dst):
    """Arc list without self-loops and duplicates, sorted by (src, dst)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    keep = src != dst
    key = np.unique(src[keep] * n + dst[keep])
    return key // n, key % n


def kronecker(scale: int, edge_factor: int = 16, seed: int = 0):
    """Graph500 Kronecker digraph.  Returns ``(n, src, dst)``: the
    distinct arcs sorted by (src, dst)."""
    n = 1 << scale
    rng = np.random.default_rng(seed)
    src, dst = _kronecker_ids(rng, scale, n * edge_factor)
    perm = rng.permutation(n).astype(np.int64)
    src, dst = perm[src], perm[dst]
    src, dst = dedup_arcs(n, src, dst)
    return n, src, dst


def relabel(n: int, src, dst, rng: np.random.Generator):
    """The same graph under a random permutation of its vertex ids."""
    perm = rng.permutation(n).astype(np.int64)
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    return perm[src], perm[dst]


def make_graph(spec: dict, seed: int):
    """The graph of a configuration file's ``graph`` entry for a run.

    The graph is drawn once from ``spec["seed"]``; the run's ``seed``
    relabels its vertices.  So every run does the same work, in another
    order.  ``kind`` names the generator: ``kronecker`` (``scale``,
    ``edge_factor``).  Returns ``{"n", "src", "dst"}``, the arcs sorted.
    """
    kind = spec["kind"]
    rng = np.random.default_rng([seed % (1 << 64), 0])
    if kind != "kronecker":
        raise ValueError(f"unknown graph kind {kind!r}")
    n, src, dst = kronecker(spec["scale"], spec["edge_factor"],
                            seed=spec["seed"])
    src, dst = dedup_arcs(n, *relabel(n, src, dst, rng))
    return {"n": n, "src": src, "dst": dst}
