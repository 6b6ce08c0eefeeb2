"""A stream of mutation batches over a fixed arc count, and the bytes a
delta census must read for each, counted from the arc lists alone.

Nothing here imports the program under test: the stream tracks its own
arc list, so the reference is given the graph the caller meant, never
one read back from the program.

* :class:`Stream` — a graph's arcs split into the present arcs and a
  held-back pool.  Each step removes arcs drawn uniformly from the
  present ones and adds arcs drawn uniformly from the pool; the removed
  arcs join the pool, so the arc count stays fixed and a state rarely
  repeats.  Drawing arcs uniformly touches a vertex in proportion to its
  degree.
* :func:`delta_bytes` — for one graph and the vertices a batch touches,
  the six CSR rows of both ends of every connected pair with an end
  touched: what the correction pass of that batch must read on that
  graph, at the least.
"""
from __future__ import annotations

import numpy as np


class Stream:
    """Present arcs and a held-back pool, as ``(k, 2)`` int64 arrays."""

    def __init__(self, n: int, src, dst, held_back: int,
                 rng: np.random.Generator):
        arcs = np.stack([np.asarray(src, np.int64),
                         np.asarray(dst, np.int64)], 1)
        held = np.zeros(len(arcs), dtype=bool)
        held[rng.choice(len(arcs), size=held_back, replace=False)] = True
        self.n = n
        self.rng = rng
        self.present = arcs[~held]
        self.pool = arcs[held]

    def draw(self, add: int, remove: int):
        """Positions ``(i, j)`` of the next batch: ``remove`` present
        arcs and ``add`` pool arcs, each without repeats."""
        i = self.rng.choice(len(self.present), size=remove, replace=False)
        j = self.rng.choice(len(self.pool), size=add, replace=False)
        return i, j

    def swap(self, i, j):
        """Apply (or, called again, undo) the batch at ``(i, j)``; returns
        ``(added, removed)``, the arcs the batch adds and removes."""
        added, removed = self.pool[j].copy(), self.present[i].copy()
        self.present[i], self.pool[j] = added, removed
        return added, removed

    def arcs(self):
        """``(n, src, dst)`` of the present arcs, a copy."""
        return self.n, self.present[:, 0].copy(), self.present[:, 1].copy()

    def snapshot(self):
        return self.present.copy(), self.pool.copy()

    def restore(self, snap) -> None:
        self.present, self.pool = snap[0].copy(), snap[1].copy()


def delta_bytes(n: int, src, dst, touched, word_bytes: int = 4) -> int:
    """Bytes the correction pass must read on the graph ``(src, dst)``
    (distinct arcs, no self-loops): for every connected pair ``u < v``
    with ``u`` or ``v`` in ``touched``, the out-, in- and undirected rows
    of both ends, one ``word_bytes`` id per entry.  As
    ``peaks.census_bytes``, restricted to the pairs a batch can change."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    und = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    r, c = und // n, und % n
    row = (np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
           + np.bincount(r, minlength=n))
    mark = np.zeros(n, dtype=bool)
    mark[np.asarray(touched, dtype=np.int64)] = True
    hit = mark[r]                        # pairs seen from a touched end
    key = np.unique(np.minimum(r[hit], c[hit]) * n
                    + np.maximum(r[hit], c[hit]))
    u, v = key // n, key % n
    return int(word_bytes * (row[u].sum() + row[v].sum()))
