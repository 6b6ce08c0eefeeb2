"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode — kernel bodies execute in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import brute_force_census, generators, triad_census
from repro.kernels import ops, ref
from repro.kernels.triad_census import SENTINEL, census_tiles_pallas


@pytest.mark.parametrize("seed,block,buckets", [
    (0, 16, (16, 64)),
    (1, 32, (32,)),
    (2, 8, (8, 32, 128)),
])
def test_census_kernel_matches_brute_force(seed, block, buckets):
    g = generators.rmat(6, edge_factor=4, seed=seed)
    want = brute_force_census(g).counts
    got = ops.triad_census_kernel(g, block=block, buckets=buckets)
    assert (got == want).all(), (got, want)


@pytest.mark.parametrize("width", [None, 128, 200, 256])
def test_census_kernel_matches_tile_oracle(width):
    """Kernel vs ref.census_tiles_ref on identical random tiles, at the
    graph's own tile width (below one 128-lane window), one full window,
    a width the kernel pads up to 256, and two full windows."""
    g = generators.erdos_renyi(60, 240, seed=3)
    from repro.core.census import canonical_dyads
    u, v = canonical_dyads(g)
    D = (len(u) // 16) * 16
    u, v = u[:D].astype(np.int32), v[:D].astype(np.int32)
    K = width or max(g.max_deg, g.max_out_deg)
    tiles = ops.build_tiles(g, u.astype(np.int64), v.astype(np.int64), K)
    args = [jnp.asarray(tiles[k]) for k in
            ("out_u", "in_u", "out_v", "in_v", "nbr_u", "nbr_v")]
    want = ref.census_tiles_ref(*args, jnp.asarray(u), jnp.asarray(v), g.n)
    # oracle takes (out_u, in_u, ... , u, v, n) in different arg order
    got = census_tiles_pallas(jnp.asarray(u), jnp.asarray(v), g.n, *args,
                              block=16, interpret=True)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("B,T,H,Hkv,D,chunk,win,dtype", [
    (2, 128, 4, 2, 64, 64, None, jnp.float32),
    (1, 256, 8, 8, 32, 128, None, jnp.float32),
    (2, 128, 4, 4, 64, 32, 48, jnp.float32),
    (1, 128, 4, 1, 128, 64, None, jnp.float32),
    (2, 64, 2, 2, 64, 64, None, jnp.bfloat16),
])
def test_flash_attention_vs_oracle(B, T, H, Hkv, D, chunk, win, dtype):
    key = jax.random.PRNGKey(B * T + H)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    qp = jnp.broadcast_to(jnp.arange(T)[None], (B, T)).astype(jnp.int32)
    want = ref.flash_attention_ref(q, k, v, qp, qp, window=win)
    got = ops.flash_attention(q, k, v, qp, qp, window=win, chunk=chunk)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert float(jnp.abs(want.astype(jnp.float32)
                         - got.astype(jnp.float32)).max()) < tol


def test_flash_attention_matches_model_chunked_path():
    """Pallas kernel == the XLA chunked_causal twin used in the models."""
    from repro.models.attention import _chunked_attention
    key = jax.random.PRNGKey(7)
    B, T, H, Hkv, D = 2, 128, 4, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    qp = jnp.broadcast_to(jnp.arange(T)[None], (B, T)).astype(jnp.int32)
    xla = _chunked_attention(q, k, v, qp, qp, None, 64, triangular=True)
    pls = ops.flash_attention(q, k, v, qp, qp, chunk=64)
    assert float(jnp.abs(xla - pls).max()) < 2e-5
