"""Partitioned (sharded-CSR) execution: concurrent shard residency,
device-side halo exchange, and out-of-core spill.

This is the device-side half of the graph-partitioning subsystem
(:mod:`repro.core.partition` builds the layout): with
``EngineConfig(partitions=P)`` the census runs as P shard passes, each
over a **local CSR** — the full rows of one contiguous vertex range plus
its halo of remote neighbor rows — with the shard's owned span of the
canonical dyad stream.  ``EngineConfig(partition_mode=...)`` picks the
execution strategy (:func:`EngineConfig.resolve_partition_mode` defaults
it):

``"pool"`` (default on the single-device backends with a device pool)
  Every shard's context is staged ONCE onto its home pool device and
  stays resident for the whole run; all shards' chunk tasks drive the
  executor's sharded workqueue **concurrently**
  (:meth:`~repro.engine.executor.Executor.run_sharded`), interleaved
  across worker threads.  Halo idx blocks are not materialized on the
  host: each shard uploads only its ptr halves and OWNED idx blocks
  (1/P of the graph) and every halo block transfers device-to-device
  from the owner shard's resident rows (``jax.device_put`` peer copy).
  Per-device memory is bounded by the largest shard context while
  aggregate pool memory holds the whole graph — the Cray-XMT
  aggregate-memory posture on a device pool.

``"mesh"`` (default on the distributed backend)
  Waves of ``n_devices`` shards execute as ONE ``shard_map`` dispatch:
  each mesh device scans its own shard's local CSR and dyad slab,
  folding into a per-device hi/lo lane — no psum (int32 lanes could
  overflow); lanes land on the primary device and merge exactly.

``"serial"`` (default whenever ``spill`` is set)
  One shard context resident at a time — the out-of-core property.  The
  context is staged once per shard (hoisted out of every per-chunk and
  per-worker path) and dispatched in-order on the primary device
  (:meth:`~repro.engine.executor.Executor.run_pinned`); the ``spill=``
  knob additionally stages each shard's dyad list through memory-mapped
  scratch files so a dyad stream larger than host RAM completes (pair
  with :func:`repro.core.graph.from_edges_mmap` for a fully out-of-core
  graph).

Every mode reuses the plan's OWN machinery end to end — the same
host-side schedules the incremental path uses (:mod:`repro.engine.delta`),
the same compiled chunk unit (every shard is padded to ONE common shard
geometry, so all shards share a single trace per plan), the same
:class:`~repro.engine.executor.Executor` fault policy (bounded retry,
device quarantine and shard re-homing, the degradation ladder) — so
every composition property holds by construction.  The whole-graph
``once`` contribution is folded exactly once; per-shard hi/lo
accumulators merge through :func:`~repro.engine.executor._merge_accs`
(exact integer folds on the primary device, bit-identical for ANY
homing, interleave, or re-home history) and ONE :func:`_acc_fetch`
completes the run — bit-identical raw bins to the unpartitioned path for
every registered op, in the same single counted device→host sync.

Correctness rests on the ``GraphOp.delta_local`` locality contract (a
dyad's contribution reads only ``{u, v} ∪ N(u) ∪ N(v)``, all of which
the halo keeps as FULL rows); plans refuse ``partitions > 1`` with any
op that opts out.  The incremental path composes: a delta's affected
dyads group by owner shard and only the owning shards dispatch —
concurrently under ``"pool"`` (:func:`subset_partitioned`).

``plan.stats["partition"]`` records the layout and the concurrency /
staging observables: ``mode``, ``h2d_puts`` (host→device context
stagings — exactly one per non-empty shard on the fault-free pool and
serial paths), ``d2d_puts`` (device-to-device halo block transfers),
``halo_host_puts`` (host-gathered halo blocks for owners with no
resident context), ``max_shard_bytes`` (the per-device residency bound),
``shard_times`` (per-shard wall-clock intervals) and ``shard_overlap``
(fraction of busy wall-clock with ≥ 2 shards in flight — the
concurrency proof the benchmark pins).
"""
from __future__ import annotations

import contextlib
import functools
import math
import os
import shutil
import tempfile
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import CSRGraph, GraphArrays
from ..core.graph import next_pow2 as _next_pow2
from ..core.partition import (GraphPartition, _gather_rows, _host,
                              build_local_arrays, halo_by_owner, local_ptrs,
                              owned_idx, partition_graph, shard_dyads)
from .executor import ChunkTask, _acc_fetch, _merge_accs

__all__ = ["full_context_bytes", "plan_partition", "run_partitioned",
           "shard_context_bytes", "subset_partitioned"]


def plan_partition(plan, g: CSRGraph) -> GraphPartition:
    """The (plan, graph) partition layout, memoized with the same
    bounded-8 weakref discipline as the reorder memo: warm runs (and
    every step of a mutation stream) pay zero partitioning cost.
    Shard count is clamped to the vertex count; metadata only is
    retained — local CSRs are rebuilt per run, one shard at a time."""
    memo = plan._partition_memo
    hit = memo.get(id(g))
    if hit is not None and hit[0]() is g:
        return hit[1]
    part = partition_graph(g, min(plan.partitions, max(g.n, 1)))
    while len(memo) >= 8:
        memo.pop(next(iter(memo)))
    memo[id(g)] = (weakref.ref(g), part)
    return part


class _Geometry:
    """Common shard device geometry: every shard pads its local idx
    arrays and dyad span to these bounds, so one plan compiles ONE trace
    of its chunk unit for all shards (the whole point of bucketing)."""

    def __init__(self, plan, part: GraphPartition):
        self.m_out = min(plan.meta.m_out_bucket,
                         _next_pow2(max((s.m_out for s in part.shards),
                                        default=1)))
        self.m_nbr = min(plan.meta.m_nbr_bucket,
                         _next_pow2(max((s.m_nbr for s in part.shards),
                                        default=1)))
        chunk = plan.chunk
        d = max(1, part.max_dyads)
        self.pad = max(chunk, -(-d // chunk) * chunk)
        if plan.backend == "distributed":
            from .backends import chunk_l
            n_dev = math.prod(plan.mesh.devices.shape)
            cl = chunk_l(plan)
            per = -(-d // n_dev)
            self.slab_l = max(cl, -(-per // cl) * cl)
            # mesh mode: each device row holds one FULL shard dyad list
            self.mesh_l = max(cl, -(-d // cl) * cl)

    def runner_kwargs(self, plan) -> dict:
        if plan.backend == "distributed":
            return {"slab_l": self.slab_l}
        return {"pad": self.pad}


def _shard_arrays(plan, g: CSRGraph, shard, geom: _Geometry) -> GraphArrays:
    """Device arrays for one shard: full-length (vertex-indexed) ptr/deg
    arrays padded to the plan's ``n_bucket`` exactly like the full path,
    over idx arrays compacted to the common shard buckets.  Vertex ids
    stay GLOBAL — kernels are untouched; non-kept rows are empty (every
    probe of them misses, which no owned dyad's reads ever do)."""
    from .plan import _pad_to
    local = build_local_arrays(g, shard.lo, shard.hi, shard.halo)
    m = plan.meta
    return GraphArrays(
        out_ptr=jnp.asarray(_pad_to(local.out_ptr, m.n_bucket + 1,
                                    local.out_ptr[-1])),
        out_idx=jnp.asarray(_pad_to(local.out_idx, geom.m_out, 0)),
        nbr_ptr=jnp.asarray(_pad_to(local.nbr_ptr, m.n_bucket + 1,
                                    local.nbr_ptr[-1])),
        nbr_idx=jnp.asarray(_pad_to(local.nbr_idx, geom.m_nbr, 0)),
        nbr_deg=jnp.asarray(_pad_to(local.nbr_deg, m.n_bucket, 0)),
        nbr_code=jnp.asarray(_pad_to(local.nbr_code, geom.m_nbr, 0)),
    )


def _once_init(plan, g: CSRGraph):
    """The whole-graph ``once`` contribution (folded into the run's
    accumulator exactly once, never once per shard).  Once kernels are
    whole-graph functions by contract, so plans carrying one pay a
    single full padded-array upload here; the per-dyad streaming — the
    memory-bound part — still runs shard-local."""
    from .delta import _zeros
    if not plan.layout.has_once:
        return _zeros(plan)
    from .backends import _once_device
    arrays = plan.padded_arrays(g)
    return _once_device(plan, *_zeros(plan), arrays, jnp.int32(g.n))


@contextlib.contextmanager
def _spill_scratch(spill):
    """Scratch directory for spilled dyad stages: ``None`` disables,
    ``True`` uses a fresh temp dir, a string roots the scratch under a
    caller-owned path.  Always removed afterwards — spill files are
    transient per-run state, never a cache."""
    if not spill:
        yield None
        return
    if isinstance(spill, str):
        os.makedirs(spill, exist_ok=True)
        d = tempfile.mkdtemp(prefix="repro-spill-", dir=spill)
    else:
        d = tempfile.mkdtemp(prefix="repro-spill-")
    try:
        yield d
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _stage_spill(u: np.ndarray, v: np.ndarray, scratch: str, tag: str):
    """Move one shard's dyad list out of RAM into an ``.npy`` memmap and
    hand back lazy read-only views — downstream padding copies from disk
    and the in-RAM list is dropped immediately."""
    path = os.path.join(scratch, f"{tag}.npy")
    d = len(u)
    mm = np.lib.format.open_memmap(path, mode="w+", dtype=np.int32,
                                   shape=(2, max(d, 1)))
    mm[0, :d] = u
    mm[1, :d] = v
    mm.flush()
    del mm
    ro = np.load(path, mmap_mode="r")
    return ro[0, :d], ro[1, :d]


# ---------------------------------------------------------------------------
# observability helpers
# ---------------------------------------------------------------------------

def _bytes_for(plan, m_out: int, m_nbr: int, dyad_slots: int) -> int:
    """int32 bytes of one resident census context with the given idx and
    dyad-slot geometry: ptr/deg halves + idx and code arrays + the dyad
    stream + the hi/lo lanes."""
    m = plan.meta
    b = 4 * (2 * (m.n_bucket + 1) + m.n_bucket)
    b += 4 * (m_out + 2 * m_nbr)
    b += 2 * 4 * dyad_slots
    b += 2 * 4 * plan.layout.total_bins
    return int(b)


def shard_context_bytes(plan, geom: _Geometry) -> int:
    """Per-device residency bound of ONE shard context — the
    ``stats["partition"]["max_shard_bytes"]`` observable the benchmark
    compares against :func:`full_context_bytes` to prove the ~P-fold
    per-device memory drop."""
    dyads = geom.mesh_l if plan.backend == "distributed" else geom.pad
    return _bytes_for(plan, geom.m_out, geom.m_nbr, dyads)


def full_context_bytes(plan) -> int:
    """Residency of the UNPARTITIONED device context under the same
    accounting — the ``partitions=1`` baseline for the memory claim."""
    m = plan.meta
    return _bytes_for(plan, m.m_out_bucket, m.m_nbr_bucket, plan.dyad_pad)


def _overlap_fraction(times: dict) -> float:
    """Fraction of busy wall-clock during which >= 2 shards were in
    flight — an interval sweep over the per-shard ``[start, end)``
    records.  0.0 for a serial (or single-shard) run, approaching
    ``(P-1)/P`` when P equal shards fully overlap."""
    ivs = [(t["start"], t["end"]) for t in times.values()
           if t["end"] > t["start"]]
    if not ivs:
        return 0.0
    events = sorted([(a, 1) for a, _ in ivs] + [(b, -1) for _, b in ivs])
    busy = multi = 0.0
    depth = 0
    prev = events[0][0]
    for x, d in events:
        if depth >= 1:
            busy += x - prev
        if depth >= 2:
            multi += x - prev
        depth += d
        prev = x
    return float(multi / busy) if busy > 0 else 0.0


# ---------------------------------------------------------------------------
# shared step closure + device-side halo exchange units
# ---------------------------------------------------------------------------

def _make_step(plan):
    """The per-chunk step closure over a ``(arrays, n, du, dv)`` shard
    context — identical to the subset runners' step, shared by the
    serial and pool drivers so every shard dispatch reuses the plan's
    compiled chunk unit."""
    if plan.backend == "pallas":
        cfg = plan.config
        interpret = cfg.resolve_interpret()
        block = cfg.resolve_block()
        chunk = max(block, (plan.chunk // block) * block)

        def step(ctx, hi, lo, t):
            a, nn, su, sv = ctx
            return plan._fn(a, nn, su, sv, jnp.int32(t.start),
                            jnp.int32(t.end), hi, lo, K=int(t.key),
                            chunk=chunk, block=block, interpret=interpret)
        return step

    def step(ctx, hi, lo, t):
        a, nn, su, sv = ctx
        return plan._fn(a, nn, su, sv, jnp.int32(t.end), jnp.int32(t.start),
                        hi, lo)
    return step


def _device_zeros(size: int, dev):
    return jax.device_put(jnp.zeros(size, jnp.int32), dev)


@functools.partial(jax.jit, static_argnames=("out_len",))
def _gather_block(ptr, idx, ids, n_ids, out_len: int):
    """Concatenated CSR rows of ``ids`` read from a shard's RESIDENT
    local arrays — the owner-side half of the device halo exchange.
    ``ids`` is pow2-padded (pad lanes repeat a valid id, masked by
    ``n_ids``); the result packs the rows back-to-back in id order —
    exactly the layout the requester's compacted idx block expects —
    with zero fill past the true total."""
    lane = jnp.arange(ids.shape[0], dtype=jnp.int32)
    starts = ptr[ids]
    counts = jnp.where(lane < n_ids, ptr[ids + 1] - starts, 0)
    cum = jnp.cumsum(counts)
    pos = jnp.arange(out_len, dtype=jnp.int32)
    row = jnp.searchsorted(cum, pos, side="right")
    row_c = jnp.clip(row, 0, ids.shape[0] - 1)
    base = jnp.where(row_c > 0, cum[jnp.maximum(row_c - 1, 0)], 0)
    src = starts[row_c] + (pos - base)
    vals = idx[jnp.clip(src, 0, idx.shape[0] - 1)]
    return jnp.where(pos < cum[-1], vals, 0).astype(jnp.int32)


@jax.jit
def _scatter_block(idx_arr, vals, start, n_valid):
    """Write ``vals[:n_valid]`` into ``idx_arr[start:start+n_valid]`` on
    device — the requester-side half of the exchange.  Pad lanes map to
    an out-of-bounds position and drop (never a clamped
    ``dynamic_update_slice``, which would corrupt the tail)."""
    lane = jnp.arange(vals.shape[0], dtype=jnp.int32)
    pos = jnp.where(lane < n_valid, start + lane, idx_arr.shape[0])
    return idx_arr.at[pos].set(vals, mode="drop")


# ---------------------------------------------------------------------------
# pool mode: concurrent shard residency across the device pool
# ---------------------------------------------------------------------------

#: the compacted idx-like arrays of a shard context and the ptr array
#: that lays each out (``nbr_code`` shares the undirected rows)
_IDX_FIELDS = (("out_idx", "out_ptr"), ("nbr_idx", "nbr_ptr"),
               ("nbr_code", "nbr_ptr"))


def _stage_pool_shard(plan, g, shard, geom, u, v, dev):
    """Phase 1 of pool staging: ONE host→device put per shard carrying
    the ptr halves (vertex-count-sized), the OWNED idx and code blocks
    (1/P of the graph — owned rows occupy the contiguous span
    ``[ptr[lo], ptr[hi])`` of the compacted layout) and the padded dyad
    stream.  The idx-like arrays are zero-initialized on device and the
    owned blocks scattered in; halo blocks arrive in phase 2,
    peer-to-peer from their owners."""
    from .plan import _pad_to
    m = plan.meta
    out_ptr, nbr_ptr, nbr_deg = local_ptrs(g, shard.lo, shard.hi, shard.halo)
    owned = owned_idx(g, shard.lo, shard.hi)
    du = np.zeros(geom.pad, np.int32)
    dv = np.ones(geom.pad, np.int32)
    du[: len(u)] = u
    dv[: len(v)] = v
    host = (_pad_to(out_ptr, m.n_bucket + 1, out_ptr[-1]),
            _pad_to(nbr_ptr, m.n_bucket + 1, nbr_ptr[-1]),
            _pad_to(nbr_deg, m.n_bucket, 0),
            tuple(_pad_to(blk, _next_pow2(max(len(blk), 1)), 0)
                  for blk in owned),
            np.int32(g.n), du, dv)
    d_optr, d_nptr, d_deg, d_blks, d_n, d_du, d_dv = jax.device_put(host,
                                                                    dev)
    w = dict(dev=dev, n=d_n, du=d_du, dv=d_dv,
             out_ptr=d_optr, nbr_ptr=d_nptr, nbr_deg=d_deg,
             host_out_ptr=out_ptr, host_nbr_ptr=nbr_ptr)
    sizes = {"out_ptr": geom.m_out, "nbr_ptr": geom.m_nbr}
    for (field, ptr), d_blk, blk in zip(_IDX_FIELDS, d_blks, owned):
        w[field] = _scatter_block(_device_zeros(sizes[ptr], dev), d_blk,
                                  jnp.int32(int(w[f"host_{ptr}"][shard.lo])),
                                  jnp.int32(len(blk)))
    return w


def _exchange_halos(plan, g, part, work, pstats):
    """Phase 2: route every (requester, owner) halo group of ids —
    contiguous both in the owner's owned span and in the requester's
    compacted layout — through an owner-device gather, a peer
    ``jax.device_put``, and a requester-device scatter.  Owners with no
    resident context (shards that own zero dyads) fall back to a
    host-side gather, counted separately as ``halo_host_puts``."""
    shards = {s.index: s for s in part.shards}
    for s, w in work.items():
        halo = shards[s].halo
        for owner, ids in halo_by_owner(part.cuts, halo):
            ow = work.get(owner)
            spans = {}
            for ptr in ("out_ptr", "nbr_ptr"):
                hp = w[f"host_{ptr}"]
                blk = int(hp[ids[0]])
                nv = int(hp[ids[-1] + 1]) - blk
                spans[ptr] = (blk, nv)
            if ow is not None:
                pad_ids = np.full(_next_pow2(max(len(ids), 1)),
                                  ids[-1], np.int32)
                pad_ids[: len(ids)] = ids
                d_ids = jax.device_put(pad_ids, ow["dev"])
                n_ids = jnp.int32(len(ids))
                vals = tuple(
                    _gather_block(ow[ptr], ow[field], d_ids, n_ids,
                                  out_len=_next_pow2(max(spans[ptr][1], 1)))
                    for field, ptr in _IDX_FIELDS)
                if ow["dev"] is not w["dev"]:
                    vals = jax.device_put(vals, w["dev"])
                    pstats["d2d_puts"] += 1
                # else: a same-device owner (P > pool width) gathers in
                # place, no transfer to count.
            else:
                # owner owns no dyads, so it was never staged: host rows
                # (identical to any resident copy) upload directly.
                ids64 = ids.astype(np.int64)
                host_vals = []
                for field, ptr in _IDX_FIELDS:
                    hp = _host(getattr(g.arrays, ptr))[: g.n + 1]
                    rows = _gather_rows(hp.astype(np.int64),
                                        getattr(g.arrays, field),
                                        ids64).astype(np.int32)
                    pad = np.zeros(_next_pow2(max(len(rows), 1)), np.int32)
                    pad[: len(rows)] = rows
                    host_vals.append(pad)
                vals = jax.device_put(tuple(host_vals), w["dev"])
                pstats["halo_host_puts"] = pstats.get("halo_host_puts",
                                                      0) + 1
            for (field, ptr), mv in zip(_IDX_FIELDS, vals):
                blk, nv = spans[ptr]
                w[field] = _scatter_block(w[field], mv, jnp.int32(blk),
                                          jnp.int32(nv))


def _finish_pool_context(plan, w):
    """Assemble one staged shard's executor context."""
    arrays = GraphArrays(**{f: w[f] for f in GraphArrays._fields})
    return (arrays, w["n"], w["du"], w["dv"])


def _host_ctx(plan, g, shard, geom, u, v, dev):
    """Full host-side shard context build — the re-home / fallback path
    (the shard's resident device is gone, so its arrays rebuild from the
    host onto ``dev``).  ``u``/``v`` must already be in dispatch order
    (the pallas schedule reorders them once, up front)."""
    from .delta import _pad_dyad_list
    arrays = _shard_arrays(plan, g, shard, geom)
    du, dv = _pad_dyad_list(plan, u, v, geom.pad)
    ctx = (arrays, jnp.int32(g.n), du, dv)
    return jax.device_put(ctx, dev)


def _pool_pass(plan, g, part, geom, shard_lists, init, pstats):
    """Concurrent pool execution of ``shard_lists`` (``[(shard, u, v)]``)
    — shared by the full run and the pool-mode delta subset.  Stages
    every shard's context onto its round-robin home device (matching
    :meth:`Executor.run_sharded`'s homing, so every first placement is a
    resident hit), exchanges halos device-to-device, then drives all
    shards' tasks through the sharded workqueue at once."""
    from .delta import _pallas_subset_schedule, _subset_tasks
    devs = plan.executor.devices
    prep = []
    for shard, u, v in shard_lists:
        if plan.backend == "pallas":
            u, v, tasks, _c, _b, _i = _pallas_subset_schedule(plan, g, u, v)
        else:
            tasks = _subset_tasks(plan, g, u, v, plan.chunk)
        prep.append((shard, np.asarray(u, dtype=np.int32),
                     np.asarray(v, dtype=np.int32), tasks))
    by_id = {shard.index: (shard, u, v) for shard, u, v, _t in prep}
    work = {}
    for k, (shard, u, v, _t) in enumerate(prep):
        work[shard.index] = _stage_pool_shard(plan, g, shard, geom, u, v,
                                              devs[k % len(devs)])
        pstats["h2d_puts"] += 1
    _exchange_halos(plan, g, part, work, pstats)
    ctxs = {s: (w["dev"], _finish_pool_context(plan, w))
            for s, w in work.items()}
    step = _make_step(plan)

    def place(s, dev):
        hit = ctxs.get(s)
        if hit is not None and hit[0] is dev:
            return hit[1]
        # re-home (or the exhausted-pool pinned rung): the old residency
        # is unreachable, so the context rebuilds from the host.
        shard, u, v = by_id[s]
        pstats["h2d_puts"] += 1
        ctx = _host_ctx(plan, g, shard, geom, u, v, dev)
        ctxs[s] = (dev, ctx)
        return ctx

    return plan.executor.run_sharded(
        [(shard.index, ts) for shard, _u, _v, ts in prep],
        place=place, step=step, init=init, pstats=pstats)


# ---------------------------------------------------------------------------
# serial mode: one resident shard at a time (the out-of-core rung)
# ---------------------------------------------------------------------------

def _serial_pass(plan, g, part, geom, shard_lists, init, pstats):
    """Serial shard loop with hoisted staging: each shard's context is
    built and placed exactly ONCE (``h2d_puts`` pins it — never per
    chunk, never per worker) and dispatched in-order on the primary
    device; exact accumulator chaining keeps bit-identity."""
    times = pstats.setdefault("shard_times", {})
    t_base = time.perf_counter()
    total = init
    if plan.backend == "distributed":
        from .backends import chunk_l
        from .delta import _subset_distributed, _zeros
        cl = chunk_l(plan)
        for shard, u, v in shard_lists:
            arrays = _shard_arrays(plan, g, shard, geom)
            pstats["h2d_puts"] += 1
            start = time.perf_counter() - t_base
            hi, lo = _subset_distributed(plan, g, u, v, arrays=arrays,
                                         init=_zeros(plan),
                                         slab_l=geom.slab_l)
            total = _merge_accs(*total, hi, lo)
            times[shard.index] = dict(start=start,
                                      end=time.perf_counter() - t_base,
                                      tasks=geom.slab_l // cl, device=0)
        return total
    from .delta import (_pad_dyad_list, _pallas_subset_schedule,
                        _subset_tasks)
    step = _make_step(plan)
    for shard, u, v in shard_lists:
        if plan.backend == "pallas":
            u, v, tasks, _c, _b, _i = _pallas_subset_schedule(plan, g, u, v)
        else:
            tasks = _subset_tasks(plan, g, u, v, plan.chunk)

        def build(shard=shard, u=u, v=v):
            arrays = _shard_arrays(plan, g, shard, geom)
            du, dv = _pad_dyad_list(plan, u, v, geom.pad)
            return (arrays, jnp.int32(g.n), du, dv)

        ctx = build()
        pstats["h2d_puts"] += 1
        start = time.perf_counter() - t_base
        total = plan.executor.run_pinned(tasks, ctx=ctx, step=step,
                                         init=total, rebuild=build)
        times[shard.index] = dict(start=start,
                                  end=time.perf_counter() - t_base,
                                  tasks=len(tasks), device=0)
    return total


# ---------------------------------------------------------------------------
# mesh mode: waves of shards across the distributed mesh
# ---------------------------------------------------------------------------

def _mesh_unit(plan):
    """The mesh-partitioned chunk unit, built once per plan and memoized
    on ``plan._mesh_part_fn``: a ``shard_map`` where each mesh device
    scans ITS OWN shard's local CSR and dyad slab through the plan's
    fused batch kernel, folding into a per-device hi/lo lane.  No psum —
    per-device lo words can exceed the hi/lo carry bound if summed in
    int32 across the mesh — so the stacked ``(n_devices, n_bins)`` lanes
    return as-is and merge exactly on the primary device."""
    if plan._mesh_part_fn is not None:
        return plan._mesh_part_fn
    from jax.sharding import PartitionSpec as P

    from .executor import _acc_update
    mesh = plan.mesh
    axes = tuple(mesh.axis_names)
    batch = plan.config.batch
    batch_fn = plan.layout.batch_kernel()
    stats = plan.stats

    def device_pass(arrays, n, u, v, valid, hi, lo):
        stats["traces"] += 1
        local = jax.tree_util.tree_map(lambda x: x[0], arrays)
        u, v, valid = u[0], v[0], valid[0]
        steps = u.shape[0] // batch

        def step(carry, xs):
            h, l = carry
            uu, vv, va = xs
            return _acc_update(h, l, batch_fn(local, n, uu, vv, va)), None

        (h, l), _ = jax.lax.scan(
            step, (hi[0], lo[0]),
            (u.reshape(steps, batch), v.reshape(steps, batch),
             valid.reshape(steps, batch)))
        return h[None], l[None]

    sh = P(axes)
    unit = jax.jit(jax.shard_map(
        device_pass, mesh=mesh,
        in_specs=(sh, P(), sh, sh, sh, sh, sh),
        out_specs=(sh, sh), check_vma=False))
    plan._mesh_part_fn = unit
    return unit


def _mesh_pass(plan, g, part, geom, shard_lists, init, pstats):
    """Mesh execution: waves of ``n_devices`` shards, each wave ONE
    stacked upload and one task sweep through the executor (retry and
    fault injection apply per chunk, as everywhere).  Within a wave all
    resident shards advance in lockstep — full overlap; short waves pad
    with inert slots (empty rows, valid=False dyads) that contribute
    nothing."""
    from .backends import chunk_l
    from .plan import _pad_to
    n_dev = math.prod(plan.mesh.devices.shape)
    cl = chunk_l(plan)
    L = geom.mesh_l
    unit = _mesh_unit(plan)
    m = plan.meta
    bins = plan.layout.total_bins
    primary = plan.executor.devices[0]
    times = pstats.setdefault("shard_times", {})
    t_base = time.perf_counter()
    total = init
    tasks = [ChunkTask(s, s + cl, float(cl * n_dev))
             for s in range(0, L, cl)]
    for wstart in range(0, len(shard_lists), n_dev):
        wave = shard_lists[wstart:wstart + n_dev]
        s_optr = np.zeros((n_dev, m.n_bucket + 1), np.int32)
        s_oidx = np.zeros((n_dev, geom.m_out), np.int32)
        s_nptr = np.zeros((n_dev, m.n_bucket + 1), np.int32)
        s_nidx = np.zeros((n_dev, geom.m_nbr), np.int32)
        s_deg = np.zeros((n_dev, m.n_bucket), np.int32)
        s_code = np.zeros((n_dev, geom.m_nbr), np.int32)
        su = np.zeros((n_dev, L), np.int32)
        sv = np.ones((n_dev, L), np.int32)
        sval = np.zeros((n_dev, L), bool)
        for d, (shard, u, v) in enumerate(wave):
            local = build_local_arrays(g, shard.lo, shard.hi, shard.halo)
            s_optr[d] = _pad_to(local.out_ptr, m.n_bucket + 1,
                                local.out_ptr[-1])
            s_oidx[d] = _pad_to(local.out_idx, geom.m_out, 0)
            s_nptr[d] = _pad_to(local.nbr_ptr, m.n_bucket + 1,
                                local.nbr_ptr[-1])
            s_nidx[d] = _pad_to(local.nbr_idx, geom.m_nbr, 0)
            s_deg[d] = _pad_to(local.nbr_deg, m.n_bucket, 0)
            s_code[d] = _pad_to(local.nbr_code, geom.m_nbr, 0)
            su[d, : len(u)] = u
            sv[d, : len(v)] = v
            sval[d, : len(u)] = True
        arrays = GraphArrays(out_ptr=jnp.asarray(s_optr),
                             out_idx=jnp.asarray(s_oidx),
                             nbr_ptr=jnp.asarray(s_nptr),
                             nbr_idx=jnp.asarray(s_nidx),
                             nbr_deg=jnp.asarray(s_deg),
                             nbr_code=jnp.asarray(s_code))
        pstats["h2d_puts"] += 1  # one stacked staging per wave
        n = jnp.int32(g.n)
        dsu, dsv, dsval = jnp.asarray(su), jnp.asarray(sv), jnp.asarray(sval)
        z = jnp.zeros((n_dev, bins), jnp.int32)

        def place(dev, ctx=(arrays, n, dsu, dsv, dsval)):
            return ctx

        def step(ctx, hi, lo, t):
            a, nn, qu, qv, qval = ctx
            cu = jax.lax.dynamic_slice(qu, (0, t.start), (n_dev, cl))
            cv = jax.lax.dynamic_slice(qv, (0, t.start), (n_dev, cl))
            cva = jax.lax.dynamic_slice(qval, (0, t.start), (n_dev, cl))
            return unit(a, nn, cu, cv, cva, hi, lo)

        w_start = time.perf_counter() - t_base
        hi_l, lo_l = plan.executor.run(tasks, place=place, step=step,
                                       init=(z, z))
        for d in range(len(wave)):
            hd, ld = jax.device_put((hi_l[d], lo_l[d]), primary)
            total = _merge_accs(*total, hd, ld)
        w_end = time.perf_counter() - t_base
        for d, (shard, _u, _v) in enumerate(wave):
            times[shard.index] = dict(start=w_start, end=w_end,
                                      tasks=len(tasks), device=d)
    return total


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def run_partitioned(plan, g: CSRGraph) -> np.ndarray:
    """The partitioned full pass — ``Plan._run_raw``'s ``partitions > 1``
    branch.  Dispatches the plan's resolved ``partition_mode`` (pool /
    mesh / serial — see the module docstring), with the executor's full
    retry/quarantine/fallback machinery inside every mode, exact
    accumulator merging across shards, ONE counted device→host sync.
    Records the layout, staging and concurrency observables in
    ``plan.stats["partition"]``."""
    if g.n_dyads == 0:  # full-run convention: all-zero bins, no sync
        return np.zeros(plan.layout.total_bins, dtype=np.int64)
    part = plan_partition(plan, g)
    geom = _Geometry(plan, part)
    mode = plan.partition_mode or "serial"
    spill = plan.config.resolve_spill()
    pstats = dict(partitions=part.parts,
                  mode=mode,
                  cuts=[int(c) for c in part.cuts],
                  shard_dyads=part.dyad_counts,
                  halo_sizes=part.halo_sizes,
                  spill=bool(spill),
                  h2d_puts=0, d2d_puts=0,
                  max_stage_bytes=0,
                  max_shard_bytes=shard_context_bytes(plan, geom),
                  stream_bytes=int(2 * 4 * g.n_dyads))
    init = _once_init(plan, g)
    with _spill_scratch(spill) as scratch:
        shard_lists = []
        for shard in part.shards:
            if shard.n_dyads == 0:
                continue
            u, v = shard_dyads(g, shard.lo, shard.hi)
            stage = int(u.nbytes + v.nbytes + 2 * 4 * geom.pad)
            pstats["max_stage_bytes"] = max(pstats["max_stage_bytes"],
                                            stage)
            if scratch is not None:
                u, v = _stage_spill(u, v, scratch, f"shard{shard.index}")
            shard_lists.append((shard, u, v))
        if not shard_lists:
            total = init
        elif mode == "pool":
            total = _pool_pass(plan, g, part, geom, shard_lists, init,
                               pstats)
        elif mode == "mesh":
            total = _mesh_pass(plan, g, part, geom, shard_lists, init,
                               pstats)
        else:
            total = _serial_pass(plan, g, part, geom, shard_lists, init,
                                 pstats)
    pstats["shard_overlap"] = _overlap_fraction(
        pstats.get("shard_times", {}))
    plan.stats["partition"] = pstats
    return _acc_fetch(plan, *total)


def subset_partitioned(plan, g: CSRGraph, u: np.ndarray, v: np.ndarray):
    """Partitioned subset pass (the delta path's runner for
    ``partitions > 1``): the affected dyads group by owner shard —
    ``searchsorted`` over the cuts — and only the owning shards build a
    local CSR and dispatch: concurrently through the pool under
    ``partition_mode="pool"``, one owner at a time otherwise (a delta
    touches FEW shards — mesh waves would run mostly empty).  Returns an
    on-device ``(hi, lo)`` pair like every subset runner (no sync;
    ``delta_correction`` owns the one fetch).
    ``stats["partition"]["delta_shards"]`` records how few shards the
    mutation actually touched."""
    from .delta import _SUBSET_RUNNERS, _zeros
    part = plan_partition(plan, g)
    geom = _Geometry(plan, part)
    init = (_once_init(plan, g) if g.n_dyads else _zeros(plan))
    if len(u) == 0 or g.n_dyads == 0:
        return init
    owner = (np.searchsorted(part.cuts, np.asarray(u, dtype=np.int64),
                             side="right") - 1)
    shard_lists = []
    for shard in part.shards:
        sel = owner == shard.index
        if sel.any():
            shard_lists.append((shard, u[sel], v[sel]))
    mode = plan.partition_mode or "serial"
    if mode == "pool" and shard_lists:
        # concurrent owner dispatch; staging/timing records go to a
        # local dict so the last FULL run's observables stay readable.
        sub = dict(h2d_puts=0, d2d_puts=0)
        total = _pool_pass(plan, g, part, geom, shard_lists, init, sub)
    else:
        runner = _SUBSET_RUNNERS[plan.backend]
        total = None
        for shard, su_, sv_ in shard_lists:
            arrays = _shard_arrays(plan, g, shard, geom)
            seed = init if total is None else _zeros(plan)
            hi, lo = runner(plan, g, su_, sv_, arrays=arrays, init=seed,
                            **geom.runner_kwargs(plan))
            total = ((hi, lo) if total is None
                     else _merge_accs(*total, hi, lo))
        if total is None:
            total = init
    pstats = plan.stats.setdefault("partition",
                                   dict(partitions=part.parts))
    pstats["delta_shards"] = len(shard_lists)
    return total
