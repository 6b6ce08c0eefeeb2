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


def _run_kernel(g, u, v, u_short, K, block, pad=0):
    """The tile kernel over dyads (u, v) with the given short sides, in
    interpret mode, plus ``pad`` padded dyads (SENTINEL endpoints, blank
    tiles, length 0)."""
    deg = np.asarray(g.arrays.nbr_deg)
    live = np.arange(len(u) + pad) < len(u)
    u = np.concatenate([u, np.full(pad, SENTINEL)]).astype(np.int32)
    v = np.concatenate([v, np.full(pad, SENTINEL)]).astype(np.int32)
    u_short = np.concatenate([u_short, np.ones(pad, bool)])
    s_rows = np.where(u_short, u, v)
    l_rows = np.where(u_short, v, u)
    tiles = ops.build_tiles(g, s_rows, l_rows, live, K)
    s_len = np.where(live, deg[np.where(live, s_rows, 0)], 0)
    l_len = np.where(live, deg[np.where(live, l_rows, 0)], 0)
    return census_tiles_pallas(
        jnp.asarray(u), jnp.asarray(v), g.n, jnp.asarray(u_short),
        jnp.asarray(tiles["short"]), jnp.asarray(tiles["long"]),
        jnp.asarray(s_len), jnp.asarray(l_len), block=block,
        interpret=True)


def _oracle(g, u, v, K):
    t = ops.build_tiles(g, u, v, np.ones(len(u), bool), K)
    return ref.census_tiles_ref(jnp.asarray(t["short"]), jnp.asarray(t["long"]),
                                jnp.asarray(u), jnp.asarray(v), g.n)


@pytest.mark.parametrize("width", [None, 128, 200, 256])
def test_census_kernel_matches_tile_oracle(width):
    """Kernel vs ref.census_tiles_ref on identical dyads, at the graph's
    own tile width (below one 128-lane window), one full window, a width
    the kernel pads up to 256, and two full windows; the short side is
    the smaller-degree endpoint, as the engine picks it."""
    g = generators.erdos_renyi(60, 240, seed=3)
    from repro.core.census import canonical_dyads
    u, v = canonical_dyads(g)
    D = (len(u) // 16) * 16
    u, v = u[:D].astype(np.int32), v[:D].astype(np.int32)
    K = width or g.max_deg
    deg = np.asarray(g.arrays.nbr_deg)
    want = _oracle(g, u, v, K)
    got = _run_kernel(g, u, v, deg[u] <= deg[v], K, block=16)
    assert (np.asarray(got) == np.asarray(want)).all()


def _probe_graph():
    """Hub 0 (degree 300: rows cross two 128-lane boundaries), vertices
    1-4 of degree exactly 1, 127, 128 and 129, arcs of every direction
    (out only, in only, mutual) on all of them, and a random sprinkle of
    arcs among the rest that closes triangles."""
    from repro.core.graph import from_edges
    n = 700
    rng = np.random.default_rng(11)
    src, dst = [], []

    def attach(x, k):
        for i, w in enumerate(rng.choice(np.arange(10, n), k,
                                         replace=False)):
            kind = i % 3  # 0: x -> w, 1: w -> x, 2: both
            if kind != 1:
                src.append(x), dst.append(w)
            if kind != 0:
                src.append(w), dst.append(x)

    attach(0, 300)
    for x, k in zip((1, 2, 3, 4), (1, 127, 128, 129)):
        attach(x, k)
    a, b = rng.integers(10, n, 1500), rng.integers(10, n, 1500)
    src += list(a) + list(b[:300])
    dst += list(b) + list(a[:300])  # 300 of them mutual
    return from_edges(n, np.array(src), np.array(dst))


def _probe_dyads(g, group):
    from repro.core.census import canonical_dyads
    u, v = canonical_dyads(g)
    deg = np.asarray(g.arrays.nbr_deg)
    rng = np.random.default_rng(len(group))
    if group == "hub_leaf":  # the hub against its smallest neighbours
        sel = np.flatnonzero(u == 0)
        sel = sel[np.argsort(deg[v[sel]], kind="stable")][:48]
    elif group == "widths":  # every row of degree 1, 127, 128, 129
        sel = np.flatnonzero((u >= 1) & (u <= 4))
        sel = sel[rng.permutation(len(sel))][:48]
        sel = np.union1d(sel, np.flatnonzero((u >= 1) & (u <= 4)
                                             & (deg[u] <= 1)))
    else:  # mutual dyads, with the hub's among them
        from repro.core.graph import dense_adjacency
        adj = dense_adjacency(g)
        mutual = np.flatnonzero(adj[u, v] & adj[v, u])
        sel = np.union1d(mutual[u[mutual] == 0][:16],
                         mutual[rng.permutation(len(mutual))][:32])
    return u[sel].astype(np.int32), v[sel].astype(np.int32)


@pytest.mark.parametrize("side", ["u", "v"])
@pytest.mark.parametrize("group", ["hub_leaf", "widths", "mutual"])
def test_census_kernel_probe_cases(group, side):
    """Hub against leaf, rows of width 1/127/128/129 (around a 128-lane
    boundary) and mutual dyads, with either endpoint's row walked as the
    short one, and padded dyads after them: the kernel's count equals
    the oracle's over the live dyads."""
    g = _probe_graph()
    deg = np.asarray(g.arrays.nbr_deg)
    assert deg[0] == 300 and list(deg[1:5]) == [1, 127, 128, 129]
    u, v = _probe_dyads(g, group)
    assert len(u) >= 16
    K = 384
    want = _oracle(g, u, v, K)
    u_short = np.full(len(u), side == "u")
    pad = (-len(u)) % 16 + 16
    got = _run_kernel(g, u, v, u_short, K, block=16, pad=pad)
    assert (np.asarray(got) == np.asarray(want)).all(), (got, want)


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
    """gather_tiles_device == build_tiles bit for bit on the
    direction-coded rows, and rows with valid == False come back
    all-SENTINEL."""
    g = _gather_test_graph()
    n = g.n
    rng = np.random.default_rng(K)
    rows = np.concatenate([np.arange(10), np.arange(n - 20, n),
                           rng.integers(10, n, 64)])
    u = rows.astype(np.int64)
    v = rng.permutation(rows).astype(np.int64)
    valid = np.arange(len(rows)) % 7 != 6
    # the rows cover what the block gather must get right
    ptr = np.asarray(g.arrays.nbr_ptr)
    start, deg = ptr[rows], ptr[rows + 1] - ptr[rows]
    last = (len(g.arrays.nbr_code) - 1) // ops.LANES * ops.LANES
    assert ((start >= last) & (deg > 0) & valid).any()
    assert ((deg == 0) & valid).any()
    assert len(set(start[valid] % ops.LANES)) > 16
    assert (deg[valid] == K).any()

    got = ops.gather_tiles_device(g.arrays, jnp.asarray(u, jnp.int32),
                                  jnp.asarray(v, jnp.int32),
                                  jnp.asarray(valid), K=K)
    want = ops.build_tiles(g, u, v, valid, K)
    assert set(got) == set(want) == {"short", "long"}
    for name, tile in want.items():
        tile_got = np.asarray(got[name])
        assert tile_got.shape == tile.shape == (len(rows), K)
        assert (tile_got[valid] == tile[valid]).all(), name
        assert (tile_got[~valid] == SENTINEL).all(), name
