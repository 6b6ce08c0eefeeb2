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

def _pad_rows(ptr, idx, rows, valid, K):
    """(len(rows), K) SENTINEL-padded tile of CSR rows (host numpy);
    rows with ``valid == False`` come back all-SENTINEL."""
    rows = np.where(valid, rows, 0)
    deg = np.where(valid, ptr[rows + 1] - ptr[rows], 0)
    out = np.full((len(rows), K), SENTINEL, dtype=np.int32)
    j = np.arange(K)
    m = j[None, :] < deg[:, None]
    pos = np.minimum(ptr[rows][:, None] + j[None, :], max(len(idx) - 1, 0))
    if len(idx):
        out[m] = idx[pos][m]
    return out


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
def gather_tiles_device(arrays, short: jax.Array, long: jax.Array,
                        valid: jax.Array, *, K: int):
    """Device-side :func:`build_tiles`: the two (B, K) direction-coded
    tiles in one trace — row ``short[i]`` and row ``long[i]`` of
    ``arrays.nbr_code`` (:class:`repro.core.graph.GraphArrays`).  Rows
    with ``valid == False`` come back all-SENTINEL, matching the host
    path's blanked padding tiles."""
    return dict(
        short=_gather_rows(arrays.nbr_ptr, arrays.nbr_code, short, valid, K),
        long=_gather_rows(arrays.nbr_ptr, arrays.nbr_code, long, valid, K),
    )


def build_tiles(g: CSRGraph, short: np.ndarray, long: np.ndarray,
                valid: np.ndarray, K: int):
    """Host-side twin of :func:`gather_tiles_device`: the (D, K)
    direction-coded tiles of rows ``short`` and ``long``."""
    ptr = np.asarray(g.arrays.nbr_ptr).astype(np.int64)
    code = np.asarray(g.arrays.nbr_code)
    short, long = (np.asarray(r, np.int64) for r in (short, long))
    return dict(short=_pad_rows(ptr, code, short, valid, K),
                long=_pad_rows(ptr, code, long, valid, K))


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
