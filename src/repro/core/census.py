"""Vectorized sub-quadratic Triad Census (Batagelj–Mrvar, paper Fig. 2.4/2.5).

The public entry point now lives in :mod:`repro.engine`
(``compile_census(graph, CensusConfig(...)).run(graph)``); ``triad_census``
here is a deprecated thin shim over it.  This module keeps the algorithm
building blocks the engine composes: the membership probe, the per-batch
census kernel, dyad enumeration/padding, and the brute-force oracle.

TPU-native reformulation of the paper's algorithm:

  * The per-dyad linked-list walks become **batched dense candidate tiles**:
    a batch of ``B`` canonical dyads gathers its two neighborhoods as
    ``(B, K)`` tiles straight from the CSR column array (``K`` = max degree,
    optionally per-bucket — see :mod:`repro.core.balance`).
  * ``IsEdge``/``IsNeighbour`` become **fixed-trip vectorized binary
    searches** over the sorted CSR rows (the paper's §4.2.4 v0.5 "faster
    searching" — binary search beat linear search there too).
  * The paper's v0.4 optimization (pre-computed dyad code, 6→4 edge probes
    in ``TriadCode``) carries over verbatim: the dyad code is computed once
    per dyad and broadcast over its ``w`` candidates.
  * The paper's "decoupled per-thread census arrays" become per-batch
    partial histograms combined by a single reduction at the end — no
    scatter contention, no atomics (TPU has none anyway).

A dedup insight the vectorization exposes: the paper's canonicality test
(line 16, Fig. 2.4) calls ``IsNeighbour(u, w)`` — but for candidates drawn
from ``N(u)`` that test is *always true* and for candidates drawn from
``N(v)`` it is exactly the union-dedup membership test.  So one membership
probe per ``N(v)`` candidate serves both the set union and the canonicality
test; candidates from ``N(u)`` need none.
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import CSRGraph, GraphArrays, dense_adjacency
from .triad_table import TRIAD_TABLE_64


class CensusResult(NamedTuple):
    """A finished triad census: ``counts[i]`` is the number of triads of
    type ``i + 1`` in MAN notation ("003" .. "300", see
    :data:`repro.core.triad_table.TRIAD_NAMES`), int64, including the
    type-003 closed form.  ``total`` always equals C(n, 3)."""

    counts: np.ndarray  # (16,) int64 — types 1..16 ("003".."300")

    @property
    def total(self) -> int:
        return int(self.counts.sum())


def make_member_fn(n_iters: int):
    """Vectorized sorted-CSR membership probe (binary search, fixed trips).

    ``member(ptr, idx, rows, queries) -> bool array`` broadcasting ``rows``
    against ``queries``; ``n_iters >= ceil(log2(max_row_len + 1))``.
    """

    def member(ptr: jax.Array, idx: jax.Array, rows: jax.Array, queries: jax.Array):
        rows_b = jnp.broadcast_to(rows, jnp.broadcast_shapes(rows.shape, queries.shape))
        q = jnp.broadcast_to(queries, rows_b.shape)
        lo = ptr[rows_b]
        hi = ptr[rows_b + 1]
        last = idx.shape[0] - 1

        def body(_, state):
            lo, hi = state
            active = lo < hi
            mid = (lo + hi) >> 1
            v = idx[jnp.clip(mid, 0, last)]
            go_right = v < q
            new_lo = jnp.where(active & go_right, mid + 1, lo)
            new_hi = jnp.where(active & ~go_right, mid, hi)
            return new_lo, new_hi

        lo, hi = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
        found = (lo < ptr[rows_b + 1]) & (idx[jnp.clip(lo, 0, last)] == q)
        return found

    return member


def _gather_neighborhood(g: GraphArrays, u: jax.Array, K: int):
    """Gather N(u) for a batch as a dense (B, K) tile + validity mask."""
    start = g.nbr_ptr[u]  # (B,)
    deg = g.nbr_deg[u]
    j = jnp.arange(K, dtype=jnp.int32)
    pos = start[:, None] + j[None, :]
    last = g.nbr_idx.shape[0] - 1
    w = g.nbr_idx[jnp.clip(pos, 0, last)]
    mask = j[None, :] < deg[:, None]
    return w, mask, deg


def make_census_batch_fn(K: int, member_iters: int, acc_dtype=jnp.int32,
                         six_probe: bool = False):
    """Build the per-batch census kernel (pure jnp; also the Pallas oracle).

    Returns ``f(graph_arrays, n, u, v, valid) -> (16,) partial counts`` for a
    batch of canonical dyads ``(u, v), u < v``.  Null triads (type 003) are
    *not* counted here — they come from the closed form at the end (paper
    line 29).

    ``six_probe=True`` disables the paper's v0.4 optimization: the dyad
    code is re-derived per candidate (6 membership probes instead of 4) —
    the pre-optimization baseline for benchmarks/run.py.
    """
    member = make_member_fn(member_iters)
    table = jnp.asarray(TRIAD_TABLE_64, dtype=jnp.int32)

    def batch_census(g: GraphArrays, n: jax.Array, u: jax.Array, v: jax.Array, valid: jax.Array):
        B = u.shape[0]
        wu, mu, deg_u = _gather_neighborhood(g, u, K)  # (B, K)
        wv, mv, deg_v = _gather_neighborhood(g, v, K)
        mu = mu & valid[:, None]
        mv = mv & valid[:, None]
        # S = N(u) ∪ N(v) \ {u, v}; N(u) never contains u, N(v) never v.
        mu = mu & (wu != v[:, None])
        mv = mv & (wv != u[:, None])
        # union dedup: drop N(v) candidates already present in N(u).
        in_nu = member(g.nbr_ptr, g.nbr_idx, u[:, None], wv)
        mv_only = mv & ~in_nu
        s_size = mu.sum(1, dtype=acc_dtype) + mv_only.sum(1, dtype=acc_dtype)  # (B,)

        # --- dyadic triads (paper lines 9-14) -------------------------------
        e_uv = member(g.out_ptr, g.out_idx, u, v)
        e_vu = member(g.out_ptr, g.out_idx, v, u)
        dyad_code = e_uv.astype(jnp.int32) + 2 * e_vu.astype(jnp.int32)  # in {1,2,3}
        # type index (0-based): mutual -> 2 ("102"), else 1 ("012")
        dyad_type = jnp.where(dyad_code == 3, 2, 1)
        dyadic = jnp.where(valid, n.astype(acc_dtype) - s_size - 2, 0)

        # --- connected triads (paper lines 15-20) ---------------------------
        # canonicality: count w iff  v<w  or  (w<v and u<w and not IsNbr(u,w)).
        canon_u = mu & (wu > v[:, None])  # w ∈ N(u) ⇒ IsNbr(u,w) true
        canon_v = mv_only & ((wv > v[:, None]) | ((wv > u[:, None]) & (wv < v[:, None])))

        def codes_for(w, canon):
            if six_probe:
                # pre-v0.4 baseline: re-derive the dyad code per candidate
                c = (member(g.out_ptr, g.out_idx, u[:, None],
                            jnp.broadcast_to(v[:, None], w.shape)).astype(jnp.int32)
                     + 2 * member(g.out_ptr, g.out_idx, v[:, None],
                                  jnp.broadcast_to(u[:, None], w.shape)).astype(jnp.int32))
            else:
                # paper v0.4: dyad code precomputed, 4 IsEdge probes remain.
                c = dyad_code[:, None]
            c = c + 4 * member(g.out_ptr, g.out_idx, u[:, None], w).astype(jnp.int32)
            c = c + 8 * member(g.out_ptr, g.out_idx, w, u[:, None]).astype(jnp.int32)
            c = c + 16 * member(g.out_ptr, g.out_idx, v[:, None], w).astype(jnp.int32)
            c = c + 32 * member(g.out_ptr, g.out_idx, w, v[:, None]).astype(jnp.int32)
            t = table[c]
            return jnp.where(canon, t, 0), canon

        t_u, m_u = codes_for(wu, canon_u)
        t_v, m_v = codes_for(wv, canon_v)

        counts = jnp.zeros((16,), dtype=acc_dtype)
        counts = counts.at[t_u.reshape(-1)].add(m_u.reshape(-1).astype(acc_dtype))
        counts = counts.at[t_v.reshape(-1)].add(m_v.reshape(-1).astype(acc_dtype))
        # masked-out lanes accumulated into bin 0 ("003"); zero it — null
        # triads come from the closed form.
        counts = counts.at[0].set(0)
        counts = counts + jnp.zeros((16,), acc_dtype).at[dyad_type].add(dyadic)
        return counts

    return batch_census


def pad_dyads(u: np.ndarray, v: np.ndarray, batch: int):
    """Pad dyad lists to a multiple of ``batch``; returns (u, v, valid)."""
    d = len(u)
    pad = (-d) % batch
    u = np.concatenate([u, np.zeros(pad, u.dtype)])
    v = np.concatenate([v, np.ones(pad, v.dtype)])  # (0,1) keeps u<v invariant
    valid = np.concatenate([np.ones(d, bool), np.zeros(pad, bool)])
    return u.astype(np.int32), v.astype(np.int32), valid


def canonical_dyads(g: CSRGraph) -> tuple[np.ndarray, np.ndarray]:
    """All canonical connected dyads (u, v) with u < v (host-side numpy)."""
    nbr_ptr = np.asarray(g.arrays.nbr_ptr)
    nbr_idx = np.asarray(g.arrays.nbr_idx)
    deg = np.diff(nbr_ptr)
    rows = np.repeat(np.arange(g.n, dtype=np.int32), deg)
    cols = nbr_idx
    keep = cols > rows
    return rows[keep], cols[keep]


@functools.partial(jax.jit, static_argnames=("out_size",))
def enumerate_dyads_device(nbr_ptr: jax.Array, nbr_idx: jax.Array,
                           m_nbr: jax.Array, *, out_size: int):
    """Device-side :func:`canonical_dyads`: jitted, fixed-shape.

    Inputs are the bucket-padded undirected CSR (see
    ``CensusPlan.padded_arrays``) plus the true entry count ``m_nbr``
    (traced, so same-bucket graphs share one trace).  Returns ``(u, v)``
    int32 arrays of static length ``out_size`` holding the canonical dyads
    in CSR row-major order — identical order to the host enumeration —
    padded past ``m_nbr // 2`` with the inert ``(0, 1)`` dyad.

    The CSR row of every entry is recovered with one vectorized
    ``searchsorted`` over the ptr array, and the ``col > row`` filter is
    compacted by gathering rank ``r``'s source position out of the running
    keep-count (a second searchsorted — all gathers, no XLA:CPU scatter,
    no data-dependent shape, no host round trip).
    """
    M = nbr_idx.shape[0]
    pos = jnp.arange(M, dtype=jnp.int32)
    rows = (jnp.searchsorted(nbr_ptr, pos, side="right") - 1).astype(jnp.int32)
    keep = (pos < m_nbr) & (nbr_idx > rows)
    csum = jnp.cumsum(keep, dtype=jnp.int32)
    rank = jnp.arange(out_size, dtype=jnp.int32)
    src = jnp.clip(jnp.searchsorted(csum, rank + 1, side="left"), 0, M - 1)
    live = rank < (m_nbr // 2)
    return (jnp.where(live, rows[src], 0),
            jnp.where(live, nbr_idx[src], 1))


@functools.partial(jax.jit, static_argnames=("ks",))
def sort_dyads_by_bucket(nbr_deg: jax.Array, u: jax.Array, v: jax.Array,
                         n_dyads: jax.Array, *, ks: tuple):
    """Device-side degree-bucket assignment + load-balancing sort.

    For each dyad the tile-width *need* is ``max(deg(u), deg(v))``, the
    longer of its two neighbour rows; its bucket is the smallest
    ``ks[i] >= need``.
    Dyads are stable-sorted by (bucket, need) — two chained stable argsorts,
    which avoids composing a single wide sort key that could overflow int32
    — so tile rows inside a chunk are degree-ordered: gathers hit
    neighboring CSR segments (coalescing) and blocks have uniform work
    (load balance).  Padding dyads sort past every real bucket.

    Returns ``(u_sorted, v_sorted, bucket_counts)`` with ``bucket_counts``
    of static length ``len(ks)`` — the only value the host needs to drive
    the per-bucket chunk loop (one scalar-array transfer per run).
    """
    need = jnp.maximum(nbr_deg[u], nbr_deg[v])
    ks_arr = jnp.asarray(ks, dtype=jnp.int32)
    b = jnp.sum(need[:, None] > ks_arr[None, :], axis=1).astype(jnp.int32)
    live = jnp.arange(u.shape[0], dtype=jnp.int32) < n_dyads
    b = jnp.where(live, b, len(ks))
    by_need = jnp.argsort(need)
    order = by_need[jnp.argsort(b[by_need])]  # stable: bucket, then need
    counts = jnp.zeros(len(ks) + 1, jnp.int32).at[b].add(1)
    return u[order], v[order], counts[: len(ks)]


def host_bucket_schedule(g: CSRGraph, ks: tuple, *,
                         with_needs: bool = True, dyads=None
                         ) -> "tuple[np.ndarray, np.ndarray | None]":
    """Host-side mirror of :func:`sort_dyads_by_bucket`'s control outputs.

    Returns ``(bucket_counts, need_sorted)``: the per-bucket dyad counts
    (identical, by construction, to the histogram the device sort
    computes — same ``need`` formula over the same live dyads) and each
    dyad's tile-width need in the device stream's (bucket, need) sort
    order.  Both are derived from the degree arrays the host already
    owns, so the pallas driver can lay out its per-bucket chunk loop —
    and the executor its cost-model chunk boundaries — **without the
    device→host control fetch** the engine used to pay (the fetch also
    serialized the pipeline: no chunk could be scheduled until the
    device sort finished).

    ``with_needs=False`` skips the O(D log D) sort and returns ``None``
    for ``need_sorted`` — the static schedule only consumes the counts.

    Operates on whatever graph it is handed: under the engine's
    ``reorder=`` preprocessing the plan passes the RELABELED graph, so
    the schedule is computed over reordered degrees and keyed (in the
    plan's per-graph memo) on the relabeled graph's identity — degree
    multisets are permutation-invariant, so bucket counts match the
    unreordered run's exactly while the per-dyad sort order follows the
    relabeled stream the device actually executes.

    ``dyads`` passes ``canonical_dyads(g)`` when the caller has them.
    """
    u, v = canonical_dyads(g) if dyads is None else dyads
    deg = np.asarray(g.arrays.nbr_deg)
    need = np.maximum(deg[u], deg[v]).astype(np.int64)
    ks_arr = np.asarray(ks, dtype=np.int64)
    b = (need[:, None] > ks_arr[None, :]).sum(1)
    counts = np.bincount(b, minlength=len(ks))[: len(ks)].astype(np.int64)
    return counts, need[np.lexsort((need, b))] if with_needs else None


def make_census_fn(g: CSRGraph, *, batch: int = 256, K: int | None = None,
                   acc_dtype=jnp.int32):
    """Build a jitted census function for graphs with this one's metadata.

    The returned fn maps ``(graph_arrays, n, u, v, valid)`` — dyads already
    padded to a multiple of ``batch`` — to per-scan-step ``(steps, 16)``
    partials (summed on host in int64 to avoid 32-bit overflow, which is the
    static-shape analogue of the paper's per-thread census arrays).
    """
    K = K or max(1, g.max_deg)
    member_iters = max(1, math.ceil(math.log2(max(g.max_deg, g.max_out_deg, 1) + 1))) + 1
    batch_fn = make_census_batch_fn(K, member_iters, acc_dtype)

    @jax.jit
    def census(arrays: GraphArrays, n: jax.Array, u: jax.Array, v: jax.Array,
               valid: jax.Array):
        steps = u.shape[0] // batch
        u_b = u.reshape(steps, batch)
        v_b = v.reshape(steps, batch)
        val_b = valid.reshape(steps, batch)

        def step(carry, xs):
            uu, vv, va = xs
            return carry, batch_fn(arrays, n, uu, vv, va)

        _, partials = jax.lax.scan(step, 0, (u_b, v_b, val_b))
        return partials  # (steps, 16)

    return census


def triad_census(g: CSRGraph, *, batch: int = 256, K: int | None = None) -> CensusResult:
    """End-to-end single-device census with host int64 accumulation.

    .. deprecated:: use ``repro.engine.compile_census(g, config).run(g)`` —
       this shim forwards to the engine's "xla" backend (and therefore gets
       the plan cache and chunked streaming for free).
    """
    from ..engine import CensusConfig, compile_census

    warnings.warn(
        "repro.core.triad_census is deprecated; use "
        "repro.engine.compile_census(graph, CensusConfig(...)).run(graph)",
        DeprecationWarning, stacklevel=2)
    cfg = CensusConfig(backend="xla", batch=batch, k=K)
    return compile_census(g, cfg).run(g)


# ----------------------------------------------------------------------------
# Brute-force oracle (paper's naive O(n^3) algorithm) for tests.
# ----------------------------------------------------------------------------

def brute_force_census(g: CSRGraph) -> CensusResult:
    a = dense_adjacency(g).astype(np.int64)
    n = g.n
    idx = np.arange(n)
    counts = np.zeros(16, dtype=np.int64)
    # vectorize over (j, k) for each i to keep memory bounded
    for i in range(n - 2):
        j, k = np.meshgrid(idx, idx, indexing="ij")
        sel = (j > i) & (k > j)
        jj, kk = j[sel], k[sel]
        code = (
            a[i, jj] + 2 * a[jj, i] + 4 * a[i, kk] + 8 * a[kk, i]
            + 16 * a[jj, kk] + 32 * a[kk, jj]
        )
        counts += np.bincount(TRIAD_TABLE_64[code], minlength=16)
    return CensusResult(counts=counts)
