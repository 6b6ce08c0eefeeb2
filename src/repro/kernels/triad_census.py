"""Pallas TPU kernel for the Triad Census inner loop (the paper's hot spot).

TPU-native design (DESIGN.md §2): instead of the GPU kernel's per-thread
linked-CSR walks + constant-memory table lookups, each grid step processes
a **block of B dyads** whose neighbourhoods arrive as dense, sentinel-padded
``(B, K)`` VMEM tiles:

  * **direction-coded rows.**  ``N(x) = OUT(x) ∪ IN(x)`` exactly, so each
    entry of an undirected row carries its own direction: the tiles hold
    ``4·w + dir_x(w)`` (``GraphArrays.nbr_code``; bit 0 ``x -> w``, bit 1
    ``w -> x``).  A candidate's triad code is ``dyad + 4·dir_u(w) +
    16·dir_v(w)`` and the dyad code is ``dir_u(v)``: the paper's four
    ``IsEdge`` probes per candidate become one row lookup, and the only
    membership test left is ``N(u)`` against ``N(v)``.  Its hit yields
    the other side's direction bits and the union dedup at once (a
    non-zero direction means the entry is in both rows).  Two tiles per
    dyad, not six;
  * **short row against long.**  Per dyad the caller orders the two rows
    by degree.  The kernel walks the *short* row a few columns at a time
    (each column a one-hot lane reduction — Mosaic has no dynamic lane
    slice) and compares them against every aligned 128-lane window of
    the *long* row, loaded once per pass: 3 vector ops per (column,
    window), adding the long entry's direction bits where the ids match.
    Both loops stop at the block's longest short / long row (per-block
    widths from the degrees, in SMEM); power-law pairs are lopsided, so
    the walk is over the smaller side;
  * **one pass per row, counts by (dir_short, dir_long).**  The long row
    is tallied as if no entry were in the short row; the short row then
    adds its entries with their true pair of directions and takes back
    what the long pass counted for entries in both rows.  Counts are kept
    per lane in 8-bit fields (one field per direction pair, at most one
    hit per lane per window), so the 64-code tally runs once per block:
    15 direction pairs, each looked up in the 64→16 isomorphism table
    (a ``(1, 128)`` vector) per dyad;
  * each grid step writes a private 16-bin partial census into one aligned
    ``(1, 128)`` output row; the host-side wrapper sums them (the paper's
    decoupled per-thread-block census).

Degree-bucketing: tiles are sized K = max degree of the *bucket*, so the
kernel is launched per degree bucket (see ops.py) — the static-allocation
idea from the paper's GPU port, minus its single global max-|S| buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.triad_table import TRIAD_TABLE_64

SENTINEL = np.int32(2**30)
LANES = 128

#: scoped-VMEM budget: at K = 16,384 the two double-buffered (32, K) int32
#: tiles and three (32, K) scratch rows take 14 MiB, past the 16 MiB
#: default once Mosaic adds its own; ask for twice the default.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024

#: short-row columns probed per pass over the long row: each window of
#: the long row is loaded once for all of them
COLUMNS = 8

#: the 64->16 isomorphism table as one lane vector (lanes 64.. unused)
_TABLE_LANES = np.pad(np.asarray(TRIAD_TABLE_64, np.int32),
                      (0, LANES - 64)).reshape(1, LANES)


def _census_kernel(n_ref, width_ref, table_ref, u_ref, v_ref, u_short_ref,
                   short_ref, long_ref, out_ref, ids_ref, dirs_ref, hit_ref):
    B, Kp = short_ref.shape
    u = u_ref[...]  # (B, 1)
    v = v_ref[...]
    us = u_short_ref[...] != 0  # u's row is the short one
    n = n_ref[0, 0]
    i = pl.program_id(0)
    n_short = (width_ref[0, i] + LANES - 1) // LANES
    n_long = (width_ref[1, i] + LANES - 1) // LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, LANES), 1)
    zeros = jnp.zeros((B, LANES), jnp.int32)

    def window(c):
        return pl.ds(pl.multiple_of(c * LANES, LANES), LANES)

    # unpack the long row once: ids and direction bits side by side
    def unpack(c, carry):
        win = window(c)
        p = long_ref[:, win]
        ids_ref[:, win] = p >> 2
        dirs_ref[:, win] = p & 3
        return carry

    jax.lax.fori_loop(0, n_long, unpack, 0)

    # probe: each short-row entry's direction in the long row (0 = absent),
    # COLUMNS short columns per pass over the long row's windows
    def columns(t, carry):
        cols = []
        for k in range(COLUMNS):
            # inside the tile; a column past every short row is SENTINEL
            # and hits nothing
            j = jnp.minimum(t * COLUMNS + k, Kp - 1)
            sel = lane == j % LANES
            win_j = window(j // LANES)
            w = jnp.sum(jnp.where(sel, short_ref[:, win_j], 0), axis=1,
                        keepdims=True) >> 2
            cols.append((sel, win_j, w))

        def probe(c, accs):
            win = window(c)
            ids, dirs = ids_ref[:, win], dirs_ref[:, win]
            return tuple(acc + jnp.where(ids == w, dirs, 0)
                         for acc, (_, _, w) in zip(accs, cols))

        accs = jax.lax.fori_loop(0, n_long, probe, (zeros,) * COLUMNS)
        for acc, (sel, win_j, _) in zip(accs, cols):
            hit = jnp.sum(acc, axis=1, keepdims=True)
            hit_ref[:, win_j] = jnp.where(sel, hit, hit_ref[:, win_j])
        return carry

    jax.lax.fori_loop(0, (width_ref[0, i] + COLUMNS - 1) // COLUMNS,
                      columns, 0)

    # A candidate w of S = N(u) ∪ N(v) \ {u, v} is counted from this dyad
    # iff w > v, or u < w < v and w is not in N(u) (the canonical rule).
    # Counts go to 8-bit lane fields keyed by (dir_short, dir_long).
    def field(d):
        return jnp.left_shift(1, d * 8)

    def long_pass(c, carry):
        size, only = carry
        win = window(c)
        w, d_long = ids_ref[:, win], dirs_ref[:, win]
        live = (d_long != 0) & (w != u) & (w != v)
        # as if w were not in the short row; the short pass corrects
        canon = live & ((w > v) | (us & (w > u) & (w < v)))
        return (size + live.astype(jnp.int32),
                only + jnp.where(canon, field(d_long), 0))

    size, only = jax.lax.fori_loop(0, n_long, long_pass, (zeros, zeros))

    def short_pass(c, carry):
        size, dyad, both, a1, a2, a3 = carry
        win = window(c)
        p = short_ref[:, win]
        w, d_short, d_long = p >> 2, p & 3, hit_ref[:, win]
        # the other endpoint's entry: the dyad's own direction bits
        dyad = dyad + jnp.where(w == jnp.where(us, v, u), d_short, 0)
        live = (d_short != 0) & (w != u) & (w != v)
        shared = d_long != 0
        size = size + (live & ~shared).astype(jnp.int32)
        f = field(d_long)
        canon = live & ((w > v) | ((w > u) & (w < v) & ~us & ~shared))
        a1 = a1 + jnp.where(canon & (d_short == 1), f, 0)
        a2 = a2 + jnp.where(canon & (d_short == 2), f, 0)
        a3 = a3 + jnp.where(canon & (d_short == 3), f, 0)
        # take back what the long pass counted for entries in both rows
        took = live & shared & ((w > v) | (us & (w > u) & (w < v)))
        return size, dyad, both + jnp.where(took, f, 0), a1, a2, a3

    size, dyad, both, a1, a2, a3 = jax.lax.fori_loop(
        0, n_short, short_pass, (size, zeros, zeros, zeros, zeros, zeros))

    def total(x):
        return jnp.sum(x, axis=1, keepdims=True)  # (B, 1)

    def count(acc, d):
        return total((acc >> (8 * d)) & 255)

    # dir_short(long endpoint): dir_u(v) when u is short, else dir_v(u),
    # whose bits are dir_u(v)'s swapped
    x = total(dyad)
    dyad_code = jnp.where(us, x, ((x & 1) << 1) | (x >> 1))
    table = table_ref[...]  # (1, 128)
    hist = zeros  # lane t of row r: dyad r's connected triads of type t
    pairs = [(0, d, count(only, d) - count(both, d)) for d in (1, 2, 3)]
    pairs += [(s, d, count(acc, d)) for s, acc in ((1, a1), (2, a2), (3, a3))
              for d in range(4)]
    for d_short, d_long, cnt in pairs:
        code = dyad_code + jnp.where(us, 4 * d_short + 16 * d_long,
                                     4 * d_long + 16 * d_short)
        kind = total(jnp.where(lane == code, table, 0))
        hist = hist + jnp.where(lane == kind, cnt, 0)
    counts = jnp.sum(hist, axis=0, keepdims=True)  # (1, 128)

    # dyadic triads: n - |S| - 2 into bin 1 ("012") or 2 ("102")
    pad_dyad = u == SENTINEL
    dyadic = jnp.where(pad_dyad, 0, n - total(size) - 2)
    is_mut = dyad_code == 3
    bin_lane = lane[:1]
    counts = counts + jnp.where(
        bin_lane == 1, jnp.sum(jnp.where(is_mut, 0, dyadic), keepdims=True), 0)
    counts = counts + jnp.where(
        bin_lane == 2, jnp.sum(jnp.where(is_mut, dyadic, 0), keepdims=True), 0)
    out_ref[...] = counts


def census_tiles_pallas(u, v, n, u_short, short, long, short_len, long_len,
                        *, block: int = 32, interpret: bool,
                        reduce: bool = True):
    """Run the census kernel over (D, K) tiles; returns (16,) partial counts.

    ``short``/``long`` hold, per dyad, the direction-coded neighbour row
    (``GraphArrays.nbr_code``) of one endpoint each, ids first and
    SENTINEL after them; ``u_short`` (D,) says the short tile is u's row.
    ``short_len``/``long_len`` (D,) are the rows' lengths (0 for padded
    dyads, whose ``u``/``v`` are SENTINEL): the kernel's loops stop at
    each block's longest row.  Which endpoint is called short is free —
    the count is exact either way — but the probe walks the short row
    column by column, so it should be the smaller.

    ``interpret=True`` executes the kernel body through the Pallas
    interpreter (CPU); on a TPU pass ``interpret=False``.  The engine
    resolves it with :meth:`repro.engine.EngineConfig.resolve_interpret`.
    ``n`` may be a traced scalar (the engine's device-resident path calls
    this under jit).  A tile width that is not a multiple of 128 is padded
    with SENTINEL columns up to one (the kernel works on aligned 128-lane
    windows).  With ``reduce=False`` the raw per-grid-step ``(grid, 16)``
    int32 partials are returned so the caller can fold them into a wider
    accumulator (the engine's hi/lo pair) instead of risking an int32
    overflow in the grid-sum.
    """
    D, K = short.shape
    if D % block:
        raise ValueError(f"dyad count {D} is not a multiple of block {block}")
    Kp = -(-K // LANES) * LANES
    if Kp // LANES > 255:
        raise ValueError(f"tile width {K} exceeds {255 * LANES}: the "
                         "kernel's 8-bit lane counts take one hit per "
                         "128-lane window")
    if Kp != K:
        short, long = (jnp.pad(t, ((0, 0), (0, Kp - K)),
                               constant_values=SENTINEL)
                       for t in (short, long))
    grid = (D // block,)
    # per grid step, the longest short row and the longest long row
    width = jnp.stack([jnp.asarray(short_len, jnp.int32),
                       jnp.asarray(long_len, jnp.int32)])
    width = jnp.minimum(width.reshape(2, grid[0], block).max(axis=2), K)
    row = pl.BlockSpec((block, 1), lambda i: (i, 0))
    tile = pl.BlockSpec((block, Kp), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array, scalar reads

    partials = pl.pallas_call(
        _census_kernel,
        grid=grid,
        in_specs=[smem, smem, pl.BlockSpec((1, LANES), lambda i: (0, 0)),
                  row, row, row, tile, tile],
        out_specs=pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block, Kp), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(n, jnp.int32).reshape(1, 1), width,
      jnp.asarray(_TABLE_LANES), u[:, None], v[:, None],
      jnp.asarray(u_short, jnp.int32)[:, None], short, long)[:, 0, :16]
    if not reduce:
        return partials  # (grid, 16)
    # decoupled-accumulator merge (paper: per-thread-block census arrays)
    return partials.sum(0)
