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


def _gather_test_graph():
    """Rows of out-degree exactly 4096, 512, 200, 128, 32 and 1
    (vertices 0-5), degree-0 rows (6-9), a random sprinkle that spreads
    row starts over every lane offset, and a last vertex with a few arcs
    each way, so that its rows start in the last 128-lane block."""
    from repro.core.graph import from_edges
    n = 4200
    src, dst = [], []
    for h, k in enumerate((4096, 512, 200, 128, 32, 1)):
        src += [h] * k
        dst += range(10, 10 + k)
    rng = np.random.default_rng(5)
    src += list(rng.integers(10, n, 3000))
    dst += list(rng.integers(10, n, 3000))
    src += [n - 1, n - 1, n - 1, n - 2, n - 3]
    dst += [20, 30, 40, n - 1, n - 1]
    return from_edges(n, np.array(src), np.array(dst))


@pytest.mark.parametrize("K", [1, 32, 128, 200, 512, 4096])
def test_device_tile_gather_matches_host_tiles(K):
    """gather_tiles_device == build_tiles bit for bit, and rows with
    valid == False come back all-SENTINEL."""
    g = _gather_test_graph()
    n = g.n
    rng = np.random.default_rng(K)
    rows = np.concatenate([np.arange(10), np.arange(n - 20, n),
                           rng.integers(10, n, 64)])
    u = rows.astype(np.int64)
    v = rng.permutation(rows).astype(np.int64)
    valid = np.arange(len(rows)) % 7 != 6
    in_ptr, in_idx = ops.build_in_csr_device(g.arrays.out_ptr,
                                             g.arrays.out_idx)
    arrays = g.arrays._replace(in_ptr=in_ptr, in_idx=in_idx)
    # the rows cover what the block gather must get right
    for ptr, idx in ((g.arrays.out_ptr, g.arrays.out_idx),
                     (in_ptr, in_idx), (g.arrays.nbr_ptr, g.arrays.nbr_idx)):
        ptr = np.asarray(ptr)
        start, deg = ptr[rows], ptr[rows + 1] - ptr[rows]
        last = (len(idx) - 1) // ops.LANES * ops.LANES
        assert ((start >= last) & (deg > 0) & valid).any()
        assert ((deg == 0) & valid).any()
        assert len(set(start[valid] % ops.LANES)) > 16
    assert (np.diff(np.asarray(g.arrays.out_ptr))[rows[valid]] == K).any()

    got = ops.gather_tiles_device(arrays, jnp.asarray(u, jnp.int32),
                                  jnp.asarray(v, jnp.int32),
                                  jnp.asarray(valid), K=K)
    want = ops.build_tiles(g, u, v, K)
    for name, tile in want.items():
        tile_got = np.asarray(got[name])
        assert tile_got.shape == tile.shape == (len(rows), K)
        assert (tile_got[valid] == tile[valid]).all(), name
        assert (tile_got[~valid] == SENTINEL).all(), name
