"""Jitted wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU (this container validates kernel
bodies in interpret mode); on a TPU backend the real kernels run.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import CSRGraph
from .flash_attention import flash_attention_pallas
from .triad_census import SENTINEL


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, q_pos, kv_pos, *, window=None, chunk=128,
                    interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return flash_attention_pallas(q, k, v, q_pos, kv_pos, window=window,
                                  block_q=chunk, block_kv=chunk,
                                  interpret=interpret)


# ----------------------------------------------------------------------------
# triad census: tile construction + degree-bucketed kernel launch
# ----------------------------------------------------------------------------

def _pad_rows(ptr, idx, rows, K):
    """(len(rows), K) tile of CSR rows padded with SENTINEL (host numpy)."""
    deg = ptr[rows + 1] - ptr[rows]
    out = np.full((len(rows), K), SENTINEL, dtype=np.int32)
    j = np.arange(K)
    m = j[None, :] < deg[:, None]
    pos = np.minimum(ptr[rows][:, None] + j[None, :], len(idx) - 1)
    vals = idx[pos]
    out[m] = vals[m]
    return out


def build_in_csr(g: CSRGraph) -> tuple[np.ndarray, np.ndarray]:
    """Transpose CSR for the IsEdge(w, u) -> w in IN(u) reformulation.

    Built once per graph and reused across streaming chunks (see
    :mod:`repro.engine.backends`).
    """
    out_ptr = np.asarray(g.arrays.out_ptr)
    out_idx = np.asarray(g.arrays.out_idx)
    rows = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(out_ptr))
    # lexsort: primary key = in-row (out_idx), secondary = in-col (rows),
    # so the transposed CSR comes out row-sorted with sorted columns.
    order = np.lexsort((rows, out_idx))
    in_rows, in_cols = out_idx[order].astype(np.int64), rows[order]
    in_ptr = np.zeros(g.n + 1, np.int64)
    np.add.at(in_ptr, in_rows + 1, 1)
    in_ptr = np.cumsum(in_ptr)
    return in_ptr, in_cols.astype(np.int32)


@jax.jit
def build_in_csr_device(out_ptr: jax.Array, out_idx: jax.Array):
    """Device-side :func:`build_in_csr`: transpose CSR from padded arrays.

    ``out_ptr``/``out_idx`` are the bucket-padded directed CSR
    (``CensusPlan.padded_arrays``); the true arc count is ``out_ptr[-1]``
    because padded ptr rows repeat the last offset.  Returns
    ``(in_ptr, in_idx)`` with the same padded shapes — padded ``in_idx``
    tail entries are inert (no real row's ptr range reaches them).  Built
    once per run, on device; no host round trip.
    """
    M = out_idx.shape[0]
    n = out_ptr.shape[0] - 1
    pos = jnp.arange(M, dtype=jnp.int32)
    rows = (jnp.searchsorted(out_ptr, pos, side="right") - 1).astype(jnp.int32)
    m = out_ptr[-1]
    # padding entries get sort key n (past every real row) so they land at
    # the array tail and outside every in_ptr range.
    cols_key = jnp.where(pos < m, out_idx, n)
    order = jnp.argsort(cols_key)  # stable: within-row cols stay sorted
    in_idx = rows[order]
    counts = jnp.zeros(n + 1, jnp.int32).at[cols_key].add(1)[:n]
    in_ptr = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts).astype(jnp.int32)])
    return in_ptr, in_idx


#: lanes of one gathered block: a CSR index array is fetched as aligned
#: rows of this many int32s, not one element at a time
LANES = 128


def gather_blocks_per_row(K: int) -> int:
    """Aligned ``LANES``-wide blocks fetched per tile row of width ``K``:
    enough to cover ``K`` entries from any offset inside the first one."""
    return -(-K // LANES) + 1


def _gather_rows(ptr, idx, rows, row_valid, K: int):
    """(B, K) SENTINEL-padded tile of CSR rows — the device ``_pad_rows``.

    The gather's cost is per index, so ``idx`` is viewed as rows of
    ``LANES`` lanes and each tile row fetches the
    :func:`gather_blocks_per_row` aligned blocks from the one holding its
    start: one gather index per block instead of one per entry.  A barrel
    shift (one static lane shift per bit of ``start % LANES``, each
    selected per row) then moves every row's first entry to lane 0.
    """
    r = jnp.where(row_valid, rows, 0)
    start = ptr[r]
    deg = ptr[r + 1] - start
    nb = gather_blocks_per_row(K)
    # tail padding keeps every block index in range: the last start lies
    # at most at len(idx), and nb - 1 blocks follow its own
    n_blocks = -(-idx.shape[0] // LANES) + nb
    blocks = jnp.pad(idx, (0, n_blocks * LANES - idx.shape[0]),
                     constant_values=SENTINEL).reshape(n_blocks, LANES)
    first = start // LANES
    w = blocks[first[:, None] + jnp.arange(nb, dtype=jnp.int32)[None, :]]
    w = w.reshape(-1, nb * LANES)
    offset = start % LANES
    for bit in range(LANES.bit_length() - 1):
        s = 1 << bit
        w = jnp.where((offset & s)[:, None] != 0, w[:, s:], w[:, :-s])
    j = jnp.arange(K, dtype=jnp.int32)
    live = row_valid[:, None] & (j[None, :] < deg[:, None])
    return jnp.where(live, w[:, :K], SENTINEL)


@functools.partial(jax.jit, static_argnames=("K",))
def gather_tiles_device(arrays, u: jax.Array, v: jax.Array,
                        valid: jax.Array, *, K: int):
    """Device-side :func:`build_tiles`: all six (B, K) tiles in one trace.

    ``arrays`` is a :class:`repro.core.graph.GraphArrays` whose
    ``in_ptr``/``in_idx`` transpose CSR is populated (see
    :func:`build_in_csr_device`).  Rows with ``valid == False`` come back
    all-SENTINEL, matching the host path's blanked padding tiles.
    """
    return dict(
        out_u=_gather_rows(arrays.out_ptr, arrays.out_idx, u, valid, K),
        in_u=_gather_rows(arrays.in_ptr, arrays.in_idx, u, valid, K),
        out_v=_gather_rows(arrays.out_ptr, arrays.out_idx, v, valid, K),
        in_v=_gather_rows(arrays.in_ptr, arrays.in_idx, v, valid, K),
        nbr_u=_gather_rows(arrays.nbr_ptr, arrays.nbr_idx, u, valid, K),
        nbr_v=_gather_rows(arrays.nbr_ptr, arrays.nbr_idx, v, valid, K),
    )


def build_tiles(g: CSRGraph, u: np.ndarray, v: np.ndarray, K: int,
                in_csr: tuple[np.ndarray, np.ndarray] | None = None):
    """All six (D, K) neighborhood tiles for a dyad batch."""
    out_ptr = np.asarray(g.arrays.out_ptr)
    out_idx = np.asarray(g.arrays.out_idx)
    nbr_ptr = np.asarray(g.arrays.nbr_ptr)
    nbr_idx = np.asarray(g.arrays.nbr_idx)
    in_ptr, in_idx = in_csr if in_csr is not None else build_in_csr(g)
    return dict(
        out_u=_pad_rows(out_ptr, out_idx, u, K),
        in_u=_pad_rows(in_ptr, in_idx, u, K),
        out_v=_pad_rows(out_ptr, out_idx, v, K),
        in_v=_pad_rows(in_ptr, in_idx, v, K),
        nbr_u=_pad_rows(nbr_ptr, nbr_idx, u, K),
        nbr_v=_pad_rows(nbr_ptr, nbr_idx, v, K),
    )


def triad_census_kernel(g: CSRGraph, *, block: int = 32,
                        buckets: tuple = (32, 128, 512),
                        interpret=None) -> np.ndarray:
    """Full 16-type census via the Pallas kernel, degree-bucketed.

    .. deprecated:: use ``repro.engine.compile_census`` with
       ``CensusConfig(backend="pallas")`` — this shim forwards there.
       Returns (16,) int64 counts.
    """
    from ..engine import CensusConfig, compile_census

    warnings.warn(
        "repro.kernels.ops.triad_census_kernel is deprecated; use "
        "repro.engine.compile_census with CensusConfig(backend='pallas')",
        DeprecationWarning, stacklevel=2)
    cfg = CensusConfig(backend="pallas", block=block, buckets=tuple(buckets),
                       interpret=interpret)
    return compile_census(g, cfg).run(g).counts
