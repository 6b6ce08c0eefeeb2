"""Graph mutations and their exact blast radius (the delta-census core).

The engine's batch pass answers "what is the census of THIS graph"; the
serving north-star is a stream of edge insertions/deletions against a
graph whose census is already known (Chin et al., arXiv:1209.6308 —
triadic analysis of *evolving* social graphs).  Because every per-dyad
kernel contribution is a pure function of the dyad's own arcs and the
arcs between ``{u, v}`` and ``N(u) ∪ N(v)`` (the paper's closed
neighborhoods), an edge-only mutation can change the contribution of a
canonical dyad ``(u, v)`` **only if u or v is an endpoint of a touched
edge** — probes against a third vertex ``w`` test membership of ``u``/
``v`` in w's rows, and any arc between ``w`` and the dyad that changed
would put ``u`` or ``v`` in the touched set by definition.  That makes
the affected set exact, not heuristic, and enumerable straight from the
undirected CSR rows of the touched vertices.

This module is pure host/NumPy: :class:`GraphDelta` (validated, deduped
edge lists), :func:`affected_dyads` (the exact canonical-dyad blast
radius on one graph), and :func:`apply_delta_csr` (the mutated
:class:`~repro.core.graph.CSRGraph`), each call inside a span
(``repro.affected``, ``repro.apply_csr``).  The device-side correction
pass lives in :mod:`repro.engine.delta`.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .graph import CSRGraph, arcs_host, from_edges
from .spans import spanned

__all__ = ["GraphDelta", "affected_dyads", "apply_delta_csr"]


def _normalize_edges(edges, what: str) -> np.ndarray:
    """Coerce an edge spec into a deduplicated ``(k, 2)`` int64 array.

    Accepts ``None``, an iterable of ``(u, v)`` pairs, or an array-like
    of shape ``(k, 2)``.  Self-loops are dropped (the census is defined
    on strict digraphs — ``from_edges`` would drop them anyway) and
    duplicate arcs collapse to one; negative endpoints are rejected here,
    upper bounds against a concrete graph in :meth:`GraphDelta.validate_for`.
    """
    if edges is None:
        return np.zeros((0, 2), dtype=np.int64)
    a = np.asarray(list(edges) if not isinstance(edges, np.ndarray) else edges,
                   dtype=np.int64)
    if a.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    if a.ndim != 2 or a.shape[1] != 2:
        raise ValueError(f"{what} must be (k, 2) arc pairs, got shape "
                         f"{a.shape}")
    if (a < 0).any():
        raise ValueError(f"{what} endpoints must be >= 0")
    a = a[a[:, 0] != a[:, 1]]  # strict digraph: self-loops are inert
    if len(a):
        a = np.unique(a, axis=0)
    return a


@dataclasses.dataclass(frozen=True)
class GraphDelta:
    """One batch of edge mutations against a fixed vertex set.

    ``edges_removed`` are applied first, then ``edges_added`` — an arc in
    both lists is present afterwards.  Removing an absent arc or adding a
    present one is a no-op (``from_edges`` deduplicates), so deltas are
    safe to replay.  Both lists are normalized at construction: ``(k, 2)``
    int64, self-loops dropped, duplicates collapsed, negatives rejected;
    endpoint upper bounds are checked against a concrete graph by
    :meth:`validate_for` (the vertex set itself never changes — grow the
    graph by rebuilding it with :func:`repro.core.graph.from_edges`).
    """

    edges_added: np.ndarray = None
    edges_removed: np.ndarray = None

    def __post_init__(self):
        object.__setattr__(self, "edges_added",
                           _normalize_edges(self.edges_added, "edges_added"))
        object.__setattr__(self, "edges_removed",
                           _normalize_edges(self.edges_removed,
                                            "edges_removed"))

    @property
    def size(self) -> int:
        """Total arcs named by the delta (after normalization)."""
        return len(self.edges_added) + len(self.edges_removed)

    @property
    def is_empty(self) -> bool:
        """True when the delta cannot change any graph it is valid for."""
        return self.size == 0

    @property
    def touched(self) -> np.ndarray:
        """Sorted unique vertex ids appearing as any named arc's endpoint —
        the seed set of the affected-dyad closure."""
        if self.is_empty:
            return np.zeros(0, dtype=np.int64)
        return np.unique(np.concatenate([self.edges_added.ravel(),
                                         self.edges_removed.ravel()]))

    def permuted(self, perm) -> "GraphDelta":
        """The same mutation expressed in relabeled vertex ids: every
        endpoint ``x`` becomes ``perm[x]``.  This is the boundary
        translation the engine's ``reorder=`` path uses — callers express
        deltas in original ids, and because
        :func:`~repro.core.graph.from_edges` is canonical over arc sets,
        applying the permuted delta to the permuted graph yields exactly
        the permutation of the mutated graph."""
        p = np.asarray(perm, dtype=np.int64)
        return GraphDelta(
            edges_added=p[self.edges_added] if len(self.edges_added)
            else self.edges_added,
            edges_removed=p[self.edges_removed] if len(self.edges_removed)
            else self.edges_removed,
        )

    def validate_for(self, g: CSRGraph) -> None:
        """Raise ``ValueError`` unless every endpoint is a vertex of ``g``."""
        if self.size and int(self.touched[-1]) >= g.n:
            raise ValueError(
                f"delta touches vertex {int(self.touched[-1])} but the graph "
                f"has n={g.n} vertices (the vertex set is fixed; rebuild via "
                "from_edges to grow it)")


@spanned("affected")
def affected_dyads(g: CSRGraph, delta: GraphDelta
                   ) -> "tuple[np.ndarray, np.ndarray]":
    """Canonical dyads of ``g`` whose kernel contribution the delta can
    change: every ``(u, v), u < v`` of ``g`` with an endpoint in
    ``delta.touched`` (see the module docstring for why this set is
    exact).  Returned as sorted ``(u, v)`` int32 arrays — order is
    irrelevant to correctness (integer accumulation) but determinism
    keeps chunk schedules reproducible.

    Dyads *created or destroyed* by the delta are handled by evaluating
    this on the old and the new graph separately
    (:func:`repro.engine.delta.delta_correction` does both): a created
    dyad appears only in the new graph's set, a destroyed one only in the
    old's, and both are incident to touched vertices by construction.
    """
    delta.validate_for(g)
    t = delta.touched
    if not len(t) or g.n_dyads == 0:
        return (np.zeros(0, dtype=np.int32),) * 2
    nbr_ptr = np.asarray(g.arrays.nbr_ptr)
    nbr_idx = np.asarray(g.arrays.nbr_idx)
    starts, ends = nbr_ptr[t], nbr_ptr[t + 1]
    deg = ends - starts
    total = int(deg.sum())
    if total == 0:
        return (np.zeros(0, dtype=np.int32),) * 2
    # vectorized multi-row CSR gather: position r of the concatenation maps
    # to starts[row(r)] + (r - cum_deg[row(r)]).
    rows = np.repeat(t, deg)
    offs = np.arange(total) - np.repeat(np.cumsum(deg) - deg, deg)
    cols = nbr_idx[np.repeat(starts, deg) + offs]
    u = np.minimum(rows, cols)
    v = np.maximum(rows, cols)
    key = np.unique(u * np.int64(g.n) + v)  # canonicalize + dedup, sorted
    return ((key // g.n).astype(np.int32), (key % g.n).astype(np.int32))


@spanned("apply_csr")
def apply_delta_csr(g: CSRGraph, delta: GraphDelta) -> CSRGraph:
    """The mutated graph: ``g``'s arcs minus ``edges_removed`` plus
    ``edges_added``, rebuilt through the same
    :func:`~repro.core.graph.from_edges` pipeline every graph enters by
    (sorted CSR rows, deduplication), so a delta-built graph is
    bit-identical to one built from the mutated edge list directly.
    The vertex count is preserved."""
    delta.validate_for(g)
    src, dst = arcs_host(g)
    if len(delta.edges_removed):
        key = src * np.int64(g.n) + dst
        rem = (delta.edges_removed[:, 0] * np.int64(g.n)
               + delta.edges_removed[:, 1])
        keep = ~np.isin(key, rem)
        src, dst = src[keep], dst[keep]
    if len(delta.edges_added):
        src = np.concatenate([src, delta.edges_added[:, 0]])
        dst = np.concatenate([dst, delta.edges_added[:, 1]])
    return from_edges(g.n, src, dst, directed=True)
