"""Published peaks of the chips the benchmark runs on, and the census's
necessary work, counted from the graph alone.

PEAKS is keyed by ``device_kind`` as JAX reports it.  A kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

import numpy as np

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 16 GB of HBM2 per chip at 819 GB/s.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud, TPU v5e documentation"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def census_bytes(n: int, src, dst, word_bytes: int = 4) -> int:
    """Bytes a triad census must read at the least: for every connected
    pair u < v, the six adjacency rows of its two ends (out-, in- and
    undirected neighbours of u and of v), one ``word_bytes`` id per entry.

    It is the same for any implementation of the per-dyad algorithm and
    takes nothing but the arc list: no tile width, bucket or backend.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    keep = src != dst
    key = np.unique(src[keep] * n + dst[keep])
    s, d = key // n, key % n
    out_deg = np.bincount(s, minlength=n)
    in_deg = np.bincount(d, minlength=n)
    und = np.unique(np.concatenate([s * n + d, d * n + s]))
    r, c = und // n, und % n
    nbr_deg = np.bincount(r, minlength=n)
    row = out_deg + in_deg + nbr_deg          # entries of a vertex's rows
    pair = r < c
    return int(word_bytes * (row[r[pair]].sum() + row[c[pair]].sum()))
