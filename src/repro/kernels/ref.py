"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.triad_table import TRIAD_TABLE_64


def census_tiles_ref(code_u, code_v, u, v, n, sentinel=jnp.int32(2**30)):
    """Oracle for the triad-census tile kernel.

    ``code_u``/``code_v``: (D, K) int32 direction-coded rows of u and v
    (``GraphArrays.nbr_code``: ``4·w + dir``, bit 0 = arc from the row's
    vertex to w, bit 1 = arc from w), padded with ``sentinel``; u, v: (D,).
    Every direction is looked up by an all-pairs compare against the rows,
    independent of the kernel's short/long walk.  Returns (16,) int32
    histogram of dyadic+connected triads (null triads come from the
    closed form outside).
    """
    def unpack(code):
        real = code != sentinel
        return jnp.where(real, code >> 2, -1), jnp.where(real, code & 3, 0)

    ids_u, dir_u = unpack(code_u)
    ids_v, dir_v = unpack(code_v)

    def lookup(cand, ids, dirs):  # direction of each candidate in a row
        hit = cand[:, :, None] == ids[:, None, :]
        return jnp.where(hit, dirs[:, None, :], 0).sum(-1)

    # S = N(u) ∪ N(v) \ {u, v}
    mu = (dir_u != 0) & (ids_u != v[:, None])
    mv = (dir_v != 0) & (ids_v != u[:, None])
    mv_only = mv & (lookup(ids_v, ids_u, dir_u) == 0)
    s_size = mu.sum(1) + mv_only.sum(1)

    dyad_code = lookup(v[:, None], ids_u, dir_u)[:, 0]
    dyad_type = jnp.where(dyad_code == 3, 2, 1)
    dyadic = n - s_size - 2

    def codes(cand, canon):
        c = (dyad_code[:, None] + 4 * lookup(cand, ids_u, dir_u)
             + 16 * lookup(cand, ids_v, dir_v))
        t = jnp.asarray(TRIAD_TABLE_64)[c]
        return jnp.where(canon, t, 0), canon

    canon_u = mu & (ids_u > v[:, None])
    canon_v = mv_only & ((ids_v > v[:, None]) |
                         ((ids_v > u[:, None]) & (ids_v < v[:, None])))
    t_u, m_u = codes(ids_u, canon_u)
    t_v, m_v = codes(ids_v, canon_v)
    counts = jnp.zeros(16, jnp.int32)
    counts = counts.at[t_u.reshape(-1)].add(m_u.reshape(-1).astype(jnp.int32))
    counts = counts.at[t_v.reshape(-1)].add(m_v.reshape(-1).astype(jnp.int32))
    counts = counts.at[0].set(0)
    counts = counts + jnp.zeros(16, jnp.int32).at[dyad_type].add(dyadic)
    return counts


def flash_attention_ref(q, k, v, q_pos, kv_pos, window=None):
    """Dense causal (optionally windowed) GQA attention oracle.

    q: (B, T, H, D); k/v: (B, S, Hkv, D); positions: (B, T)/(B, S).
    """
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, T, Hkv, H // Hkv, D)
    s = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    mask = kv_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask &= kv_pos[:, None, :] > q_pos[:, :, None] - window
    s = jnp.where(mask[:, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", w, v.astype(jnp.float32))
    return o.reshape(B, T, H, D).astype(q.dtype)
