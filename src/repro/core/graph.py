"""CSR graph representation — the TPU-native analogue of the paper's §2.5 data
structures.

The paper replaced pointer-chasing adjacency linked lists with CSR
(adjacency-array) storage on the GPU and cache-blocked lists on the CPU.  On
TPU there is no pointer chasing at all: the graph lives as flat device arrays
(CSR ``ptr``/``idx`` pairs with sorted columns), and every probe the paper did
with a list walk becomes either a vectorized binary search over the sorted
CSR rows (HBM path) or a dense tile compare (Pallas/VMEM path).

Two CSRs are kept, mirroring the paper's implementation (Fig. 4.1):
  * ``out_ptr/out_idx``   — directed out-arcs, used by ``IsEdge(u, v)``.
  * ``nbr_ptr/nbr_idx``   — open undirected neighborhoods ``N(u)``
                            (union of in- and out-arcs), used for the
                            candidate set ``S`` and ``IsNeighbour``.

The undirected rows also carry a direction-coded twin, ``nbr_code``:
entry ``4·w + dir_u(w)`` beside each ``w`` of ``N(u)``, where bit 0 of
``dir_u(w)`` says ``u -> w`` and bit 1 says ``w -> u``.  Since
``N(u) = OUT(u) ∪ IN(u)`` exactly, one lookup in ``N(u)`` answers both
directed probes of the pair, which is what the Pallas census kernel reads.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .spans import span


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (minimum 1) — the metadata bucket and
    batch-width rounding rule shared by plan keys and the batched path."""
    return 1 << max(0, int(x) - 1).bit_length() if x > 1 else 1


#: vertex count up to which ``nbr_code`` is exact: the largest code,
#: ``4·(n-1) + 3``, must stay below the kernels' int32 ``SENTINEL`` 2**30.
MAX_CODED_VERTICES = 1 << 28


class GraphArrays(NamedTuple):
    """Device-resident graph (a JAX pytree; all int32).

    ``nbr_code`` shares ``nbr_ptr`` with ``nbr_idx``: entry ``4·w + dir``
    for each ``w`` of ``nbr_idx``, ``dir`` = 1 (arc ``u -> w`` only), 2
    (``w -> u`` only) or 3 (mutual).  Exact while ``n <=
    MAX_CODED_VERTICES``; the Pallas backend, its reader, refuses larger
    graphs (``Plan`` demotes them to xla).
    """

    out_ptr: jax.Array  # (n+1,)
    out_idx: jax.Array  # (m,) sorted within each row
    nbr_ptr: jax.Array  # (n+1,)
    nbr_idx: jax.Array  # (m_nbr,) sorted within each row
    nbr_deg: jax.Array  # (n,) undirected open-neighborhood sizes
    nbr_code: jax.Array  # (m_nbr,) 4·nbr_idx + direction bits


@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Host-side graph container: static metadata + device arrays."""

    n: int
    m: int  # number of directed arcs
    m_nbr: int  # total undirected adjacency entries (2 * #undirected edges)
    max_deg: int  # max undirected open-neighborhood size
    max_out_deg: int
    arrays: GraphArrays

    @property
    def n_dyads(self) -> int:
        """Number of canonical connected dyads (undirected edges)."""
        return self.m_nbr // 2


def _row_ptr(rows: np.ndarray, n: int) -> np.ndarray:
    """int32 CSR row pointers of ``n`` rows from sorted row ids."""
    ptr = np.zeros(n + 1, np.int32)
    ptr[1:] = np.cumsum(np.bincount(rows, minlength=n))
    return ptr


def _build_host_arrays(n: int, src, dst, *, directed: bool = True):
    """The host-side (numpy) half of :func:`from_edges`: canonicalize the
    arc list and build both CSRs.  Returns ``(host GraphArrays, m, m_nbr,
    max_deg, max_out_deg)`` — shared by the device-resident and
    memory-mapped constructors so both are canonical over arc sets.

    Two sorts in all: ``np.unique`` puts the distinct arc keys in CSR
    order, and one sort of tagged keys gives the neighbour rows.  The
    tagged keys reach ``4·n² - 1``, which uint64 holds for every ``n`` an
    int32 id can name."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if not directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    n64 = np.int64(n)
    keep = src != dst
    # the distinct arcs as sorted keys src·n + dst: the out-CSR in order
    key = np.unique(src[keep] * n64 + dst[keep])
    src, out_idx = np.divmod(key, n64)

    # undirected open neighborhoods, each entry with its direction bits:
    # every arc seen from its source (4·key + 1, out) and from its target
    # (4·reverse key + 2, in), sorted together.  A mutual pair gives
    # 4k + 1 and 4k + 2 side by side, the only adjacent entries that
    # differ by 1, and folds into 4k + 3.  An entry 4·(x·n + w) + dir is
    # x·4n + (4·w + dir), so one divmod by 4n splits row from code.
    rev = out_idx * n64 + src
    tagged = np.concatenate([4 * key.view(np.uint64) + 1,
                             4 * rev.view(np.uint64) + 2])
    tagged.sort()
    pair = np.flatnonzero(np.diff(tagged) == 1)
    tagged[pair] += 2
    rows, code = np.divmod(np.delete(tagged, pair + 1), np.uint64(4 * n))
    out_ptr, nbr_ptr = _row_ptr(src, n), _row_ptr(rows.view(np.int64), n)
    deg = nbr_ptr[1:] - nbr_ptr[:-1]
    out_deg = out_ptr[1:] - out_ptr[:-1]
    arrays = GraphArrays(out_ptr=out_ptr, out_idx=out_idx.astype(np.int32),
                         nbr_ptr=nbr_ptr, nbr_idx=(code >> 2).astype(np.int32),
                         nbr_deg=deg, nbr_code=code.astype(np.int32))
    return (arrays, int(key.size), int(code.size),
            int(deg.max()) if n and deg.size else 0,
            int(out_deg.max()) if n and out_deg.size else 0)


def from_edges(n: int, src, dst, *, directed: bool = True) -> CSRGraph:
    """Build a :class:`CSRGraph` from arc lists.

    Self-loops are dropped (the algorithm targets strict digraphs) and
    duplicate arcs are deduplicated, as in the paper's pre-processing stage.
    For ``directed=False`` every edge is materialized as a mutual dyad.
    """
    with span("from_edges"):
        host, m, m_nbr, max_deg, max_out_deg = _build_host_arrays(
            n, src, dst, directed=directed)
        arrays = GraphArrays(*(jnp.asarray(a) for a in host))
    return CSRGraph(n=n, m=m, m_nbr=m_nbr, max_deg=max_deg,
                    max_out_deg=max_out_deg, arrays=arrays)


def from_edges_mmap(n: int, src, dst, *, directed: bool = True,
                    dir: "str | None" = None) -> CSRGraph:
    """Build a :class:`CSRGraph` whose arrays are **memory-mapped** host
    ``.npy`` files — the out-of-core constructor.

    Canonicalization is identical to :func:`from_edges` (same helper, so
    the two are bit-identical over the same arc set); the CSR arrays are
    then written to ``dir`` (a fresh temp directory when ``None``) and
    reopened read-only with ``mmap_mode="r"``, so the returned graph
    holds O(1) resident RAM per array and pages rows in on demand.  The
    partitioned engine (:mod:`repro.engine.partition`) and
    :func:`arcs_host_iter` slice these arrays one vertex range at a time,
    which is what lets a dyad stream larger than host RAM complete.
    Numpy treats a memmap as an ndarray and jax converts lazily, so an
    mmap-backed graph is accepted everywhere a device-backed one is — at
    the cost of a host→device upload on first full-array use.
    """
    import os
    import tempfile

    host, m, m_nbr, max_deg, max_out_deg = _build_host_arrays(
        n, src, dst, directed=directed)
    d = dir if dir is not None else tempfile.mkdtemp(prefix="repro-graph-")
    os.makedirs(d, exist_ok=True)

    def spill(name: str, arr: np.ndarray):
        if arr.size == 0:  # np.memmap rejects zero-length buffers
            return arr
        path = os.path.join(d, f"{name}.npy")
        mm = np.lib.format.open_memmap(path, mode="w+", dtype=arr.dtype,
                                       shape=arr.shape)
        mm[:] = arr
        mm.flush()
        del mm
        return np.load(path, mmap_mode="r")

    arrays = GraphArrays(**{f: spill(f, v) for f, v in
                            zip(GraphArrays._fields, host)})
    return CSRGraph(n=n, m=m, m_nbr=m_nbr, max_deg=max_deg,
                    max_out_deg=max_out_deg, arrays=arrays)


def arcs_host(g: CSRGraph) -> "tuple[np.ndarray, np.ndarray]":
    """Recover the directed arc list ``(src, dst)`` as host int64 arrays
    from the out-CSR — the exact inverse of :func:`from_edges` for
    deduplicated strict digraphs.  Used by graph rewrites that re-enter
    ``from_edges`` (delta application, vertex relabeling): slicing to
    ``g.n + 1`` / ``g.m`` keeps this correct on bucket-padded arrays."""
    out_ptr = np.asarray(g.arrays.out_ptr)[: g.n + 1]
    dst = np.asarray(g.arrays.out_idx)[: g.m].astype(np.int64)
    src = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(out_ptr))
    return src, dst


def arcs_host_iter(g: CSRGraph, *, cuts=None, block: int = 1 << 16):
    """Stream the directed arc list shard-at-a-time: yields one
    ``(src, dst)`` int64 pair per contiguous vertex range, reading only
    that range's CSR rows per step — O(range) resident host memory on an
    mmap-backed graph (:func:`from_edges_mmap`), where :func:`arcs_host`
    would materialize the full list.  Ranges come from ``cuts`` (e.g.
    :func:`repro.core.partition.partition_cuts`, to iterate exactly the
    engine's shards) or fixed ``block``-sized strides.  Concatenating
    every yield reproduces :func:`arcs_host` exactly."""
    ptr = g.arrays.out_ptr
    ptr = (ptr if isinstance(ptr, np.ndarray)
           else np.asarray(ptr))[: g.n + 1].astype(np.int64)
    idx = g.arrays.out_idx
    if not isinstance(idx, np.ndarray):  # fetch device arrays ONCE
        idx = np.asarray(idx)
    bounds = (np.asarray(cuts, dtype=np.int64) if cuts is not None
              else np.arange(0, g.n + block, block,
                             dtype=np.int64).clip(max=g.n))
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        lo, hi = int(lo), int(hi)
        if hi <= lo:
            continue
        dst = np.asarray(idx[ptr[lo]:ptr[hi]], dtype=np.int64)
        src = np.repeat(np.arange(lo, hi, dtype=np.int64),
                        np.diff(ptr[lo:hi + 1]))
        yield src, dst


def stack_graph_arrays(arrays: "list[GraphArrays]") -> GraphArrays:
    """Stack per-graph :class:`GraphArrays` into one batched pytree.

    Every field gains a leading batch axis ``B = len(arrays)``; the inputs
    must already share identical (bucket-padded) shapes — i.e. come from
    one plan's ``padded_arrays``/``padded_arrays_host`` — which is exactly
    the same-bucket admission rule ``CensusPlan.run_batch`` enforces.
    Host (numpy) members are stacked on host and shipped as ONE device
    put per field — the cheap path for fleet batching; device members are
    stacked with ``jnp.stack``.
    """
    def stk(field):
        vals = [getattr(a, field) for a in arrays]
        if all(isinstance(v, np.ndarray) for v in vals):
            return jnp.asarray(np.stack(vals))
        return jnp.stack(vals)

    return GraphArrays(**{f: stk(f) for f in GraphArrays._fields})


def dense_adjacency(g: CSRGraph) -> np.ndarray:
    """(n, n) boolean adjacency — for small-graph oracles only."""
    a = np.zeros((g.n, g.n), dtype=bool)
    ptr = np.asarray(g.arrays.out_ptr)
    idx = np.asarray(g.arrays.out_idx)
    for u in range(g.n):
        a[u, idx[ptr[u] : ptr[u + 1]]] = True
    return a


def load_pajek_or_edgelist(path: str) -> CSRGraph:
    """Minimal loader for Pajek ``*Vertices/*Arcs/*Edges`` or ``u v`` lines.

    Handles the paper's 0-/1-indexed distinction (§5.1.1): Pajek files are
    1-indexed, plain edge lists are taken as 0-indexed unless a header says
    otherwise.
    """
    srcs: list[int] = []
    dsts: list[int] = []
    undirected_rows: list[int] = []
    n = 0
    mode = "edges"
    pajek = False
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith(("#", "%")):
                continue
            low = line.lower()
            if low.startswith("*vertices"):
                n = int(line.split()[1])
                pajek = True
                mode = "vertices"  # skip vertex-label lines until *arcs/*edges
                continue
            if low.startswith("*arcs"):
                mode = "arcs"
                continue
            if low.startswith("*edges"):
                mode = "undirected"
                continue
            if line.startswith("*"):
                mode = "skip"
                continue
            if mode in ("skip", "vertices"):
                continue
            parts = line.split()
            if len(parts) < 2:
                continue
            u, v = int(parts[0]), int(parts[1])
            if pajek:
                u, v = u - 1, v - 1
            srcs.append(u)
            dsts.append(v)
            if mode == "undirected":
                undirected_rows.append(len(srcs) - 1)
    src = np.array(srcs, dtype=np.int64)
    dst = np.array(dsts, dtype=np.int64)
    if undirected_rows:
        extra = np.array(undirected_rows)
        src = np.concatenate([src, dst[extra]])
        dst = np.concatenate([dst, np.array(srcs, dtype=np.int64)[extra]])
    if not n:
        n = int(max(src.max(initial=-1), dst.max(initial=-1)) + 1)
    return from_edges(n, src, dst, directed=True)
