"""Pallas TPU kernel for the Triad Census inner loop (the paper's hot spot).

TPU-native design (DESIGN.md §2): instead of the GPU kernel's per-thread
linked-CSR walks + constant-memory table lookups, each grid step processes
a **block of B dyads** whose neighborhoods arrive as dense, sentinel-padded
``(B, K)`` VMEM tiles:

  * every ``IsEdge``/``IsNeighbour`` probe is a broadcast compare against a
    VMEM-resident row tile — 8x128-lane VPU work, no gather, no divergence
    (the four directed probes were rewritten as memberships in
    OUT(u)/IN(u)/OUT(v)/IN(v), all *block-loadable* rows).  The kernel
    walks the row tiles one column at a time and accumulates into
    ``(B, K)`` VMEM scratch, so fast memory stays O(B·K) at every tile
    width; every value it touches is 2-D with a 128-aligned lane window,
    which is what Mosaic lowers.  Rows are left-packed, so both loops stop
    at the block's longest row: past it every tile holds only SENTINEL;
  * CSR rows hold distinct ids, so a candidate matches at most one column
    of each row: the four probe bits of a triad code are *summed* into one
    code accumulator per candidate tile (weights 4/8/16/32) instead of kept
    as four masks;
  * the 64->16 isomorphism mapping is a loop over the 64 codes — each
    code's count lands in its type's lane of a ``(1, 128)`` census vector,
    the type read from the table held in SMEM (no vector gather, no
    scatter);
  * each grid step writes a private 16-bin partial census into one aligned
    ``(1, 128)`` output row; the host-side wrapper sums them (the paper's
    decoupled per-thread-block census).

Degree-bucketing: tiles are sized K = max degree of the *bucket*, so the
kernel is launched per degree bucket (see ops.py) — the static-allocation
idea from the paper's GPU port, minus its single global max-|S| buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.triad_table import TRIAD_TABLE_64

SENTINEL = np.int32(2**30)
LANES = 128

#: scoped-VMEM budget: six double-buffered (32, 8192) int32 tiles (12 MiB)
#: plus three (32, 8192) accumulators (3 MiB) leave the 16 MiB default no
#: headroom (v5e refuses the kernel at 14 MiB), so ask for twice that.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _census_kernel(n_ref, table_ref, width_ref, u_ref, v_ref, out_u_ref,
                   in_u_ref, out_v_ref, in_v_ref, nbr_u_ref, nbr_v_ref,
                   out_ref, code_u_ref, code_v_ref, dup_ref):
    B = nbr_u_ref.shape[0]
    u = u_ref[...]  # (B, 1)
    v = v_ref[...]
    n = n_ref[0, 0]
    width = width_ref[0, pl.program_id(0)]  # longest row in this block
    n_chunks = (width + LANES - 1) // LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, LANES), 1)
    bin_lane = lane[:1]  # (1, 128): lane t holds triad type t's count

    def window(c):
        return pl.ds(pl.multiple_of(c * LANES, LANES), LANES)

    # probe accumulators: the triad-code bits of each candidate of N(u)
    # and N(v), and the N(v)-in-N(u) union-dedup hits.
    code_u_ref[...] = jnp.zeros_like(code_u_ref)
    code_v_ref[...] = jnp.zeros_like(code_v_ref)
    dup_ref[...] = jnp.zeros_like(dup_ref)

    def column(j, carry):
        # column j of a row tile as a (B, 1) value: aligned 128-lane load,
        # then a one-hot lane reduction (Mosaic has no dynamic lane slice).
        sel = lane == j % LANES
        win = window(j // LANES)

        def col(ref):
            return jnp.sum(jnp.where(sel, ref[:, win], 0), axis=1,
                           keepdims=True)

        r_nbr_u = col(nbr_u_ref)
        r_out_u, r_in_u = col(out_u_ref), col(in_u_ref)
        r_out_v, r_in_v = col(out_v_ref), col(in_v_ref)

        def code(w):
            return (jnp.where(w == r_out_u, 4, 0)
                    + jnp.where(w == r_in_u, 8, 0)
                    + jnp.where(w == r_out_v, 16, 0)
                    + jnp.where(w == r_in_v, 32, 0))

        def cand(c, carry):
            win = window(c)
            w_u = nbr_u_ref[:, win]
            w_v = nbr_v_ref[:, win]
            code_u_ref[:, win] += code(w_u)
            code_v_ref[:, win] += code(w_v)
            dup_ref[:, win] += (w_v == r_nbr_u).astype(jnp.int32)
            return carry

        return jax.lax.fori_loop(0, n_chunks, cand, carry)

    jax.lax.fori_loop(0, width, column, 0)

    # dyad code (paper v0.4: computed once per dyad, 4 probes left per w)
    def hit(ref, x):
        return jnp.max(jnp.where(ref[...] == x, 1, 0), axis=1, keepdims=True)

    dyad_code = hit(out_u_ref, v) + 2 * hit(out_v_ref, u)  # (B, 1)
    pad_dyad = u == SENTINEL

    def epilogue(c, carry):
        s_size, counts = carry
        win = window(c)
        nbr_u = nbr_u_ref[:, win]
        nbr_v = nbr_v_ref[:, win]
        mu = (nbr_u != SENTINEL) & (nbr_u != v)
        mv = (nbr_v != SENTINEL) & (nbr_v != u)
        mv_only = mv & (dup_ref[:, win] == 0)
        s_size = (s_size + jnp.sum(mu.astype(jnp.int32), axis=1, keepdims=True)
                  + jnp.sum(mv_only.astype(jnp.int32), axis=1, keepdims=True))
        canon_u = mu & (nbr_u > v) & ~pad_dyad
        canon_v = (mv_only & ((nbr_v > v) | ((nbr_v > u) & (nbr_v < v)))
                   & ~pad_dyad)
        c_u = jnp.where(canon_u, dyad_code + code_u_ref[:, win], -1)
        c_v = jnp.where(canon_v, dyad_code + code_v_ref[:, win], -1)

        def tally(code, counts):
            h = (jnp.sum(jnp.where(c_u == code, 1, 0), keepdims=True)
                 + jnp.sum(jnp.where(c_v == code, 1, 0), keepdims=True))
            return counts + jnp.where(bin_lane == table_ref[0, code], h, 0)

        return s_size, jax.lax.fori_loop(0, 64, tally, counts)

    s_size, counts = jax.lax.fori_loop(
        0, n_chunks, epilogue,
        (jnp.zeros((B, 1), jnp.int32), jnp.zeros((1, LANES), jnp.int32)))

    # dyadic triads: n - |S| - 2 into bin 1 ("012") or 2 ("102")
    dyadic = jnp.where(pad_dyad, 0, n - s_size - 2)
    is_mut = dyad_code == 3
    counts = counts + jnp.where(
        bin_lane == 1, jnp.sum(jnp.where(is_mut, 0, dyadic), keepdims=True), 0)
    counts = counts + jnp.where(
        bin_lane == 2, jnp.sum(jnp.where(is_mut, dyadic, 0), keepdims=True), 0)
    out_ref[...] = counts


def census_tiles_pallas(u, v, n, out_u, in_u, out_v, in_v, nbr_u, nbr_v,
                        *, block: int = 32, interpret: bool,
                        reduce: bool = True):
    """Run the census kernel over (D, K) tiles; returns (16,) partial counts.

    Tile rows hold a CSR row's ids first and SENTINEL after them.

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
    D, K = nbr_u.shape
    if D % block:
        raise ValueError(f"dyad count {D} is not a multiple of block {block}")
    tiles = [out_u, in_u, out_v, in_v, nbr_u, nbr_v]
    Kp = -(-K // LANES) * LANES
    if Kp != K:
        tiles = [jnp.pad(t, ((0, 0), (0, Kp - K)), constant_values=SENTINEL)
                 for t in tiles]
    grid = (D // block,)
    # per grid step, the longest row among its dyads' six tiles
    width = functools.reduce(jnp.maximum, [
        jnp.sum(t != SENTINEL, axis=1, dtype=jnp.int32) for t in tiles])
    width = width.reshape(1, grid[0], block).max(axis=2)
    row = pl.BlockSpec((block, 1), lambda i: (i, 0))
    tile = pl.BlockSpec((block, Kp), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array, scalar reads

    partials = pl.pallas_call(
        _census_kernel,
        grid=grid,
        in_specs=[smem, smem, smem, row, row] + [tile] * 6,
        out_specs=pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block, Kp), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(n, jnp.int32).reshape(1, 1),
      jnp.asarray(TRIAD_TABLE_64, jnp.int32).reshape(1, 64), width,
      u[:, None], v[:, None], *tiles)[:, 0, :16]
    if not reduce:
        return partials  # (grid, 16)
    # decoupled-accumulator merge (paper: per-thread-block census arrays)
    return partials.sum(0)
