"""Contiguous vertex-range graph partitioning with halo exchange (host side).

The paper's Patents-scale result (and the Cray-XMT comparison it anchors)
lives at sizes where the whole CSR cannot sit on one device, so the graph
itself — not just the dyad stream — must be sharded.  This module is the
host-side half of that subsystem: it cuts the vertex id space into
``parts`` contiguous ranges balanced by **owned canonical dyads**, and
builds, per shard, a *local* CSR holding the full rows of the owned range
plus a **halo** of remote rows its dyads read.

Why contiguous ranges: canonical dyads ``(u, v), v > u`` are enumerated
in row order, so a contiguous vertex range owns a contiguous span of the
canonical dyad stream — the cuts come straight out of a cumulative-sum +
``searchsorted`` over per-row owned-dyad counts, and a locality-aware
relabeling (``EngineConfig(reorder=...)``, applied upstream of
partitioning) doubles as a partitioner: neighbors relabeled close
together land in the same shard and shrink every halo.

Why the halo is exactly ``range ∪ partners ∪ N(range ∪ partners)``: every
chunk kernel's contribution for a dyad ``(u, v)`` reads only rows of
``{u, v} ∪ N(u) ∪ N(v)`` — the same locality contract
``GraphOp.delta_local`` declares for the incremental path (see
:mod:`repro.engine.ops`).  For owned dyads, ``u`` is in the range, ``v``
is a partner, and every probed third vertex ``w`` is a neighbor of one of
them; keeping those rows IN FULL (never truncated) means membership
probes see exactly the global CSR row and results are bit-identical to
the unpartitioned pass.  The pallas census path reads the
direction-coded twin of the same rows (``nbr_code``), kept in full
beside ``nbr_idx``.

Everything here is plain numpy over host views of the graph arrays —
memory-mapped graphs (:func:`repro.core.graph.from_edges_mmap`) stream
through these routines one shard at a time without materializing the
full arc list in RAM.  Device-side execution lives in
:mod:`repro.engine.partition`.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .graph import CSRGraph, GraphArrays

__all__ = ["GraphPartition", "ShardInfo", "build_local_arrays",
           "halo_by_owner", "halo_vertices", "local_ptrs", "owned_idx",
           "partition_cuts", "partition_graph", "shard_dyads"]


def _host(a) -> np.ndarray:
    """Host view of a graph array: numpy (incl. ``np.memmap``) passes
    through untouched — slicing stays lazy for mmap-backed graphs — and a
    device array is fetched once."""
    return a if isinstance(a, np.ndarray) else np.asarray(a)


def partition_cuts(g: CSRGraph, parts: int) -> np.ndarray:
    """``parts + 1`` vertex boundaries with near-equal owned-dyad counts.

    Vertex ``u`` owns the canonical dyads ``(u, v), v > u, v ∈ N(u)``;
    cutting the cumulative owned-count curve at even targets balances the
    *work* (dyads), not the vertex count — the degree-skew analogue of
    the paper's dynamic scheduling, applied to data placement.  Returns
    a monotone int64 array ``[0, c_1, ..., c_{parts-1}, n]``; duplicate
    boundaries (an empty shard) are legal and skipped at execution.
    """
    parts = max(1, int(parts))
    ptr = _host(g.arrays.nbr_ptr)[: g.n + 1].astype(np.int64)
    idx = _host(g.arrays.nbr_idx)
    owned = np.zeros(g.n, dtype=np.int64)
    block = 1 << 18  # rows per sweep: bounded RAM even on mmap graphs
    for lo in range(0, g.n, block):
        hi = min(lo + block, g.n)
        a, b = int(ptr[lo]), int(ptr[hi])
        cols = np.asarray(idx[a:b], dtype=np.int64)
        rows = np.repeat(np.arange(lo, hi, dtype=np.int64),
                         np.diff(ptr[lo:hi + 1]))
        counts = np.bincount(rows[cols > rows] - lo, minlength=hi - lo)
        owned[lo:hi] = counts
    cum = np.concatenate([[0], np.cumsum(owned)])
    targets = cum[-1] * np.arange(1, parts, dtype=np.float64) / parts
    cuts = np.searchsorted(cum, targets, side="left")
    return np.concatenate([[0], cuts, [g.n]]).astype(np.int64)


def shard_dyads(g: CSRGraph, lo: int, hi: int):
    """Canonical dyads owned by the vertex range ``[lo, hi)``, in global
    ids and canonical (row-major) order — the contiguous span of the full
    stream this shard owns.  Reads only the range's CSR rows, so an
    mmap-backed graph pages in O(range) bytes."""
    ptr = _host(g.arrays.nbr_ptr)[: g.n + 1].astype(np.int64)
    a, b = int(ptr[lo]), int(ptr[hi])
    cols = np.asarray(_host(g.arrays.nbr_idx)[a:b])
    rows = np.repeat(np.arange(lo, hi, dtype=np.int32),
                     np.diff(ptr[lo:hi + 1]))
    keep = cols > rows
    return rows[keep].astype(np.int32), cols[keep].astype(np.int32)


def _gather_rows(ptr: np.ndarray, idx, verts: np.ndarray) -> np.ndarray:
    """Concatenated CSR rows of ``verts`` (sorted unique int64 ids),
    via one vectorized position expansion — no per-vertex python loop."""
    starts = ptr[verts]
    counts = ptr[verts + 1] - starts
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    cum = np.concatenate([[0], np.cumsum(counts)])
    pos = np.repeat(starts - cum[:-1], counts) + np.arange(total)
    return np.asarray(idx[pos], dtype=np.int64)


def halo_vertices(g: CSRGraph, lo: int, hi: int,
                  partners: np.ndarray) -> np.ndarray:
    """Sorted remote row ids the shard ``[lo, hi)`` must keep locally.

    ``partners`` are the ``v`` endpoints of the shard's owned dyads.  The
    kernels read rows of ``{u, v} ∪ N(u) ∪ N(v)`` per dyad, so the halo
    is ``(partners ∪ N(range ∪ partners))`` minus the owned range —
    every membership probe target, neighborhood gather, and (via
    ``w ∈ N(u)``) every in-arc source row of the owned endpoints.
    """
    ptr = _host(g.arrays.nbr_ptr)[: g.n + 1].astype(np.int64)
    own = np.arange(lo, hi, dtype=np.int64)
    ends = np.union1d(own, np.asarray(partners, dtype=np.int64))
    third = _gather_rows(ptr, _host(g.arrays.nbr_idx), ends)
    needed = np.union1d(ends, third)
    return needed[(needed < lo) | (needed >= hi)]


def halo_by_owner(cuts: np.ndarray, halo: np.ndarray) -> "list[tuple[int, np.ndarray]]":
    """Group a shard's halo row ids by their OWNER shard — the ownership
    metadata the device-side halo exchange routes on.

    Contiguous vertex-range ownership makes this a ``searchsorted`` over
    the cuts: halo id ``w`` is owned by the shard whose range contains it,
    and because ``halo`` is sorted, each owner's ids form one contiguous
    slice.  Returns ``[(owner_index, ids), ...]`` for owners with at
    least one requested row, in owner order — each entry is one
    (requester, owner) exchange: the owner's resident device arrays hold
    the rows in full, so the rows transfer device-to-device
    (``jax.device_put`` peer copy), never through the host."""
    halo = np.asarray(halo, dtype=np.int64)
    if len(halo) == 0:
        return []
    owner = np.searchsorted(np.asarray(cuts), halo, side="right") - 1
    bounds = np.flatnonzero(np.diff(owner)) + 1
    groups = np.split(halo, bounds)
    return [(int(owner[0 if i == 0 else bounds[i - 1]]), grp)
            for i, grp in enumerate(groups)]


def local_ptrs(g: CSRGraph, lo: int, hi: int, halo: np.ndarray):
    """The O(n) ptr half of a shard's local CSR — ``(out_ptr, nbr_ptr,
    nbr_deg)`` exactly as :func:`build_local_arrays` lays them out, but
    WITHOUT gathering any idx entries.

    The device-side halo exchange stages these host-derived ptr arrays
    (cheap, vertex-count-sized) and fills the idx arrays on device: the
    owned block from one host upload, every halo block from the owner
    shard's resident device rows.  The idx layout they describe is the
    concatenation of kept rows in vertex-id order, so the block of rows
    owned by shard ``o`` (range ``[lo_o, hi_o)``) occupies the contiguous
    span ``[ptr[lo_o], ptr[hi_o])`` of the compacted idx array —
    block offsets come straight off these ptrs."""
    keep = np.union1d(np.arange(lo, hi, dtype=np.int64),
                      np.asarray(halo, dtype=np.int64))

    def sub(ptr_full):
        ptr = _host(ptr_full)[: g.n + 1].astype(np.int64)
        counts = ptr[keep + 1] - ptr[keep]
        new_counts = np.zeros(g.n, dtype=np.int64)
        new_counts[keep] = counts
        return np.concatenate([[0], np.cumsum(new_counts)]).astype(np.int32)

    out_ptr = sub(g.arrays.out_ptr)
    nbr_ptr = sub(g.arrays.nbr_ptr)
    nbr_deg = (nbr_ptr[1:] - nbr_ptr[:-1]).astype(np.int32)
    return out_ptr, nbr_ptr, nbr_deg


def owned_idx(g: CSRGraph, lo: int, hi: int):
    """Concatenated idx entries of the OWNED rows ``[lo, hi)`` only —
    ``(out_block, nbr_block, code_block)`` int32 — the single host→device
    upload a pool-mode shard pays (1/P of the graph; halo blocks arrive
    device-to-device from their owners)."""
    verts = np.arange(lo, hi, dtype=np.int64)
    out_ptr = _host(g.arrays.out_ptr)[: g.n + 1].astype(np.int64)
    nbr_ptr = _host(g.arrays.nbr_ptr)[: g.n + 1].astype(np.int64)
    return tuple(_gather_rows(ptr, _host(idx), verts).astype(np.int32)
                 for ptr, idx in ((out_ptr, g.arrays.out_idx),
                                  (nbr_ptr, g.arrays.nbr_idx),
                                  (nbr_ptr, g.arrays.nbr_code)))


def build_local_arrays(g: CSRGraph, lo: int, hi: int,
                       halo: np.ndarray) -> GraphArrays:
    """Shard-local CSR as host numpy: full-length ptr/deg arrays (rows
    outside ``range ∪ halo`` are empty — binary search sees ``lo == hi``
    and every probe of them misses, which no owned dyad ever does) over
    **compacted** idx arrays holding only the kept rows' entries.  Kept
    rows are bit-identical to the global CSR rows, so every kernel probe
    answers exactly as on the full graph."""
    keep = np.union1d(np.arange(lo, hi, dtype=np.int64),
                      np.asarray(halo, dtype=np.int64))

    def sub(ptr_full, idx_full):
        ptr = _host(ptr_full)[: g.n + 1].astype(np.int64)
        starts = ptr[keep]
        counts = ptr[keep + 1] - starts
        local_idx = _gather_rows(ptr, _host(idx_full), keep).astype(np.int32)
        new_counts = np.zeros(g.n, dtype=np.int64)
        new_counts[keep] = counts
        new_ptr = np.concatenate(
            [[0], np.cumsum(new_counts)]).astype(np.int32)
        return new_ptr, local_idx

    out_ptr, out_idx = sub(g.arrays.out_ptr, g.arrays.out_idx)
    nbr_ptr, nbr_idx = sub(g.arrays.nbr_ptr, g.arrays.nbr_idx)
    _, nbr_code = sub(g.arrays.nbr_ptr, g.arrays.nbr_code)
    nbr_deg = (nbr_ptr[1:] - nbr_ptr[:-1]).astype(np.int32)
    return GraphArrays(out_ptr=out_ptr, out_idx=out_idx, nbr_ptr=nbr_ptr,
                       nbr_idx=nbr_idx, nbr_deg=nbr_deg, nbr_code=nbr_code)


@dataclasses.dataclass(frozen=True)
class ShardInfo:
    """Static per-shard metadata (the dyad lists and local CSR are rebuilt
    per run — a cached plan must never pin graph-sized host memory)."""

    index: int
    lo: int              # owned vertex range [lo, hi)
    hi: int
    n_dyads: int         # owned canonical dyads
    halo: np.ndarray     # sorted remote row ids kept locally
    m_out: int           # local out-CSR entries (owned ∪ halo rows)
    m_nbr: int           # local nbr-CSR entries

    @property
    def halo_size(self) -> int:
        return int(len(self.halo))


@dataclasses.dataclass(frozen=True)
class GraphPartition:
    """A graph's partition layout: cuts plus per-shard :class:`ShardInfo`.

    Built by :func:`partition_graph` (and memoized per (plan, graph) by
    the engine — see ``Plan._partition_memo``).  Holds metadata only:
    cuts, dyad counts, halo id lists and local array sizes — O(n) worst
    case, never O(m)."""

    parts: int
    cuts: np.ndarray
    shards: "tuple[ShardInfo, ...]"

    @property
    def dyad_counts(self) -> "list[int]":
        return [s.n_dyads for s in self.shards]

    @property
    def halo_sizes(self) -> "list[int]":
        return [s.halo_size for s in self.shards]

    @property
    def max_dyads(self) -> int:
        return max([s.n_dyads for s in self.shards] or [0])


def partition_graph(g: CSRGraph, parts: int) -> GraphPartition:
    """Cut ``g`` into ``parts`` contiguous vertex-range shards with halos.

    One pass per shard over its owned rows + halo rows; the returned
    layout is all an executor needs to rebuild any shard's local CSR
    independently (out-of-core: one shard resident at a time)."""
    cuts = partition_cuts(g, parts)
    ptrs = (_host(g.arrays.out_ptr)[: g.n + 1].astype(np.int64),
            _host(g.arrays.nbr_ptr)[: g.n + 1].astype(np.int64))
    shards = []
    for i in range(len(cuts) - 1):
        lo, hi = int(cuts[i]), int(cuts[i + 1])
        u, v = shard_dyads(g, lo, hi)
        halo = halo_vertices(g, lo, hi, np.unique(v))
        keep = np.union1d(np.arange(lo, hi, dtype=np.int64), halo)
        m_out = int((ptrs[0][keep + 1] - ptrs[0][keep]).sum())
        m_nbr = int((ptrs[1][keep + 1] - ptrs[1][keep]).sum())
        shards.append(ShardInfo(index=i, lo=lo, hi=hi, n_dyads=int(len(u)),
                                halo=halo, m_out=m_out, m_nbr=m_nbr))
    return GraphPartition(parts=len(shards), cuts=cuts,
                          shards=tuple(shards))
