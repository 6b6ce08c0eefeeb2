"""Plain reference triad census: NumPy on the host, in blocks of dyads.

It shares no code with the program under test.  It takes a directed arc
list and follows Batagelj and Mrvar's sub-quadratic algorithm dyad by
dyad, vectorised over a block of dyads at a time:

    for each connected pair u < v:
        S = N(u) | N(v) - {u, v}
        the n - |S| - 2 triads {u, v, w} with w outside S hold only the
        pair's own arcs: type 012 or 102;
        every w in S with v < w, or with u < w < v and w not in N(u),
        is counted once: the type of the triad {u, v, w};
    type 003 = C(n, 3) - all the others.

Counts are exact int64.  ``dedup=False`` gives the benchmark's control:
the same pass without the union test of ``w in N(u)``, which counts some
connected triads twice and so breaks the exactness the configurations
state.
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("003", "012", "102", "021D", "021U", "021C", "111D", "111U",
         "030T", "030C", "201", "120D", "120U", "120C", "210", "300")

# Each class is fixed by the multiset of (out-degree, in-degree) of its
# three vertices (Holland and Leinhardt's definitions, statnet's naming of
# 111D = A<->B<-C and 111U = A<->B->C).
_SIGNATURES = {
    "003": [(0, 0), (0, 0), (0, 0)],
    "012": [(1, 0), (0, 1), (0, 0)],
    "102": [(1, 1), (1, 1), (0, 0)],
    "021D": [(2, 0), (0, 1), (0, 1)],
    "021U": [(0, 2), (1, 0), (1, 0)],
    "021C": [(1, 0), (1, 1), (0, 1)],
    "111D": [(1, 1), (1, 2), (1, 0)],
    "111U": [(1, 1), (2, 1), (0, 1)],
    "030T": [(2, 0), (1, 1), (0, 2)],
    "030C": [(1, 1), (1, 1), (1, 1)],
    "201": [(2, 2), (1, 1), (1, 1)],
    "120D": [(2, 0), (1, 2), (1, 2)],
    "120U": [(0, 2), (2, 1), (2, 1)],
    "120C": [(2, 1), (1, 1), (1, 2)],
    "210": [(2, 1), (1, 2), (2, 2)],
    "300": [(2, 2), (2, 2), (2, 2)],
}


def code_table() -> np.ndarray:
    """Type index (0..15, in ``NAMES`` order) of each 6-bit triad code.

    Bit 0: x->y, 1: y->x, 2: x->z, 3: z->x, 4: y->z, 5: z->y.
    """
    by_sig = {tuple(sorted(s)): NAMES.index(k) for k, s in _SIGNATURES.items()}
    pairs = [(0, 1), (1, 0), (0, 2), (2, 0), (1, 2), (2, 1)]
    table = np.zeros(64, dtype=np.int64)
    for code in range(64):
        out = [0, 0, 0]
        inn = [0, 0, 0]
        for bit, (a, b) in enumerate(pairs):
            if code >> bit & 1:
                out[a] += 1
                inn[b] += 1
        table[code] = by_sig[tuple(sorted(zip(out, inn)))]
    return table


TABLE = code_table()


class ArcSet:
    """Membership of pairs ``(x, y)`` in a set of directed pairs over
    ``n`` vertices: a dense bit matrix where it is small, else a sorted
    key array."""

    DENSE_LIMIT = 1 << 30  # n * n cells, one byte each

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        self.n = n
        self.dense = n * n <= self.DENSE_LIMIT
        if self.dense:
            self.bits = np.zeros((n, n), dtype=bool)
            self.bits[src, dst] = True
        else:
            self.keys = np.unique(src * np.int64(n) + dst)

    def __call__(self, x: np.ndarray, y: np.ndarray) -> np.ndarray:
        if self.dense:
            return self.bits[x, y]
        q = x * np.int64(self.n) + y
        i = np.searchsorted(self.keys, q)
        return self.keys[np.minimum(i, len(self.keys) - 1)] == q


def _ragged(ptr: np.ndarray, rows: np.ndarray):
    """For each row in ``rows``, the positions ``ptr[r] .. ptr[r+1]-1``:
    returns ``(owner, pos)``, ``owner[j]`` the index into ``rows``."""
    lens = ptr[rows + 1] - ptr[rows]
    owner = np.repeat(np.arange(len(rows)), lens)
    start = np.repeat(ptr[rows] - np.cumsum(lens) + lens, lens)
    return owner, start + np.arange(int(lens.sum()))


def triad_census(n: int, src, dst, *, dedup: bool = True,
                 block_candidates: int = 1 << 23) -> np.ndarray:
    """The 16 triad counts of a directed graph, int64, in ``NAMES`` order.

    ``src``/``dst`` are the arcs; self-loops and repeated arcs are
    ignored.  Dyads are processed in blocks of about ``block_candidates``
    candidate third vertices, so memory stays bounded on large graphs.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    arc = ArcSet(n, src, dst)
    # undirected neighbourhoods N(x) as a CSR over sorted pair keys
    und = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    nb_row, nb_col = und // n, und % n
    nb_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(nb_row, minlength=n), out=nb_ptr[1:])
    nbr = ArcSet(n, nb_row, nb_col)
    deg = np.diff(nb_ptr)
    pair = nb_row < nb_col
    du, dv = nb_row[pair], nb_col[pair]          # connected dyads, u < v

    counts = np.zeros(16, dtype=np.int64)
    work = np.cumsum(deg[du] + deg[dv])
    bounds = np.searchsorted(work, np.arange(0, work[-1] if len(work) else 0,
                                             block_candidates), side="left")
    bounds = np.unique(np.concatenate([bounds, [len(du)]]))
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        u, v = du[lo:hi], dv[lo:hi]
        e_uv = arc(u, v).astype(np.int64)
        e_vu = arc(v, u).astype(np.int64)
        dyad_code = e_uv + 2 * e_vu
        # w from N(u): in S unless w == v; counted when v < w
        ou, pu = _ragged(nb_ptr, u)
        wu = nb_col[pu]
        cu = wu > v[ou]
        # w from N(v): in S unless w == u or w in N(u)
        ov, pv = _ragged(nb_ptr, v)
        wv = nb_col[pv]
        in_nu = nbr(u[ov], wv)
        both = np.bincount(ov, weights=in_nu,
                           minlength=len(u)).astype(np.int64)
        new_v = (wv != u[ov]) & (~in_nu if dedup else True)
        cv = new_v & ((wv > v[ov]) | (wv > u[ov]))
        s_size = (deg[u] - 1) + (deg[v] - 1) - (both if dedup else 0)
        dyadic = n - s_size - 2
        counts[2] += int(dyadic[dyad_code == 3].sum())
        counts[1] += int(dyadic[dyad_code != 3].sum())
        for owner, w, sel in ((ou, wu, cu), (ov, wv, cv)):
            o, w = owner[sel], w[sel]
            uu, vv = u[o], v[o]
            code = (dyad_code[o] + 4 * arc(uu, w) + 8 * arc(w, uu)
                    + 16 * arc(vv, w) + 32 * arc(w, vv))
            counts += np.bincount(TABLE[code], minlength=16)
    counts[0] = math.comb(n, 3) - int(counts[1:].sum())
    return counts
