"""Incremental delta census: the affected-subset pass + exact correction.

:func:`delta_correction` turns a :class:`~repro.core.delta.GraphDelta`
into the exact int64 correction vector for a plan's cached raw bins:

    raw(new) == raw(old) + delta_correction(plan, g_old, g_new, delta)

bit for bit, for every registered :class:`~repro.engine.ops.GraphOp`, on
every backend.  The machinery is the plan's OWN streaming pipeline —
same compiled chunk unit (``plan._fn``), same
:class:`~repro.engine.executor.Executor` dispatch (static or dynamic
schedule, same device pool), same int32 hi/lo accumulator discipline —
restricted to the affected canonical dyads
(:func:`repro.core.delta.affected_dyads`) instead of the full stream.
Two subset passes run entirely on device (old graph's affected dyads
into one zero-initialized accumulator, new graph's into another, per-run
``once`` contributions folded into each like any full run), their
normalized difference is computed on device (:func:`_acc_diff` —
arithmetic-shift carries make the hi/lo form exact for negative totals),
and ONE device→host transfer fetches the correction — a delta
application costs exactly the one counted sync a full run costs, on work
proportional to the mutation's footprint.

Why subtraction is exact: every kernel is pure integer arithmetic over
the dyad's local structure, so an unaffected dyad contributes the same
value to both graphs and cancels without ever being computed; the
affected dyads are re-evaluated on both graphs and their old
contribution is subtracted exactly (``(hi, lo)`` with ``hi`` possibly
negative still packs to the exact int64 — arithmetic right-shift
normalization keeps ``0 <= lo < 2**30``).

The entry point users see is :meth:`repro.engine.Plan.apply_delta`,
which adds the cost-model fallback (``EngineConfig.delta_threshold``)
and returns a :class:`DeltaResult`.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import balance
from ..core.delta import GraphDelta, affected_dyads, apply_delta_csr
from ..core.graph import CSRGraph
from ..core.spans import span, spanned
from .executor import _ACC_SHIFT, ChunkTask, _acc_fetch
from .faults import InjectedFault, resolve_faults

__all__ = ["DeltaResult", "delta_correction"]


class DeltaResult(NamedTuple):
    """Outcome of one :meth:`repro.engine.Plan.apply_delta` application.

    ``graph`` is the mutated :class:`~repro.core.graph.CSRGraph`, ``raw``
    the updated fused int64 bins (pass both back into the next
    ``apply_delta`` to keep streaming), ``results`` the per-op finalized
    results for the new graph (identical to ``plan.run(graph)``),
    ``mode`` is ``"delta"`` (affected-subset correction) or ``"full"``
    (fallback recompute), and ``affected_fraction`` the footprint that
    drove the choice — affected dyads over the larger of the two dyad
    streams."""

    graph: CSRGraph
    raw: np.ndarray
    results: dict
    mode: str
    affected_fraction: float


@jax.jit
def _acc_diff(hi_n, lo_n, hi_o, lo_o):
    """Normalized hi/lo difference (new minus old), on device.

    Both inputs satisfy ``0 <= lo < 2**30``; the raw difference's lo word
    lies in ``(-2**30, 2**30)`` so the arithmetic-shift carry is in
    ``{-1, 0}`` and the result again satisfies the invariant, with ``hi``
    carrying the (possibly negative) sign — ``(hi << 30) + lo`` is the
    exact integer difference."""
    lo = lo_n - lo_o
    carry = lo >> _ACC_SHIFT
    return hi_n - hi_o + carry, lo - (carry << _ACC_SHIFT)


def affected_fraction(g_old: CSRGraph, g_new: CSRGraph,
                      n_old: int, n_new: int) -> float:
    """Mutation footprint: affected dyads over the larger dyad stream.

    The delta pass walks the affected set twice (old + new graph), so its
    break-even against one full pass sits near 0.5 — the default
    ``EngineConfig.delta_threshold``."""
    denom = max(g_old.n_dyads, g_new.n_dyads, 1)
    return max(n_old, n_new) / denom


def _pad_dyad_list(plan, u: np.ndarray, v: np.ndarray, pad=None):
    """Affected dyads padded to the plan's device dyad-list shape.

    The compiled chunk units were traced with ``(dyad_pad,)`` dyad
    streams; handing them the same shape means the subset pass reuses the
    full pass's executables with zero retraces.  Padding entries are the
    inert ``(0, 1)`` dyad, never covered by any task span.  ``pad``
    overrides the target length — the partitioned engine pads every
    shard's dyad span to ONE common length so all shards share a single
    trace of the chunk unit."""
    pad = plan.dyad_pad if pad is None else int(pad)
    du = np.zeros(pad, dtype=np.int32)
    dv = np.ones(pad, dtype=np.int32)
    du[: len(u)] = u
    dv[: len(v)] = v
    return jnp.asarray(du), jnp.asarray(dv)


def _subset_tasks(plan, g: CSRGraph, u: np.ndarray, v: np.ndarray,
                  chunk: int) -> "list[ChunkTask]":
    """Chunk schedule over the affected list ``[0, len(u))`` — the
    fixed-size grid under the static schedule, cost-model boundaries
    (per-dyad degree weights, as in the full pass) under dynamic."""
    D = len(u)
    if plan.config.schedule == "dynamic" and D:
        w = balance.dyad_weights(g, u, v, plan.config.weight_model)
        bounds = balance.chunk_bounds_by_cost(w, chunk)
        cum = np.concatenate([[0.0], np.cumsum(w, dtype=np.float64)])
        return [ChunkTask(int(a), int(b), float(cum[b] - cum[a]))
                for a, b in zip(bounds[:-1], bounds[1:])]
    return [ChunkTask(s, min(s + chunk, D), float(min(s + chunk, D) - s))
            for s in range(0, D, chunk)]


def _zeros(plan):
    z = jnp.zeros(plan.layout.total_bins, jnp.int32)
    return z, z


def _subset_xla(plan, g: CSRGraph, u: np.ndarray, v: np.ndarray, *,
                arrays=None, init=None, pad=None):
    """xla subset pass -> (hi, lo): once contribution + affected chunks.

    The keyword overrides are the partitioned engine's hooks
    (:mod:`repro.engine.partition`): ``arrays`` substitutes a shard-local
    CSR for the full padded arrays, ``init`` a pre-folded accumulator for
    the per-run once fold (so the whole-graph once contribution lands
    exactly once across shards, not once per shard), and ``pad`` a common
    shard dyad-list length."""
    from .backends import _once_device

    if g.n_dyads == 0:  # match the full-run convention: all-zero raw bins
        return _zeros(plan) if init is None else init
    if arrays is None:
        arrays = plan.padded_arrays(g)
    n = jnp.int32(g.n)
    du, dv = _pad_dyad_list(plan, u, v, pad)
    if init is None:
        init = _once_device(plan, *_zeros(plan), arrays, n)

    def place(dev):
        ctx = (arrays, n, du, dv)
        return ctx if dev is None else jax.device_put(ctx, dev)

    def step(ctx, hi, lo, t):
        a, nn, su, sv = ctx
        return plan._fn(a, nn, su, sv, jnp.int32(t.end), jnp.int32(t.start),
                        hi, lo)

    return plan.executor.run(_subset_tasks(plan, g, u, v, plan.chunk),
                             place=place, step=step, init=init)


def _subset_distributed(plan, g: CSRGraph, u: np.ndarray, v: np.ndarray, *,
                        arrays=None, init=None, slab_l=None):
    """distributed subset pass: affected dyads dealt round-robin into the
    ``(n_devices, L)`` slab layout the shard_map unit was traced for.

    ``arrays``/``init`` as in :func:`_subset_xla`; ``slab_l`` pins the
    per-device slab length so every shard of a partitioned run shares one
    trace (excess slab slots carry the validity-masked inert dyad)."""
    from .backends import _once_device, chunk_l

    if g.n_dyads == 0:
        return _zeros(plan) if init is None else init
    n_dev = math.prod(plan.mesh.devices.shape)
    cl = chunk_l(plan)
    D = len(u)
    if slab_l is None:
        # per-device slab: ceil(D / n_dev), rounded up to whole chunks
        per = -(-max(D, 1) // n_dev)
        L = max(cl, -(-per // cl) * cl)
    else:
        L = int(slab_l)
    tu = np.zeros((n_dev, L), dtype=np.int32)
    tv = np.ones((n_dev, L), dtype=np.int32)
    tval = np.zeros((n_dev, L), dtype=bool)
    r = np.arange(D)
    tu[r % n_dev, r // n_dev] = u
    tv[r % n_dev, r // n_dev] = v
    tval[r % n_dev, r // n_dev] = True
    if arrays is None:
        arrays = plan.padded_arrays(g)
    n = jnp.int32(g.n)
    dtu, dtv, dtval = jnp.asarray(tu), jnp.asarray(tv), jnp.asarray(tval)
    if init is None:
        init = _once_device(plan, *_zeros(plan), arrays, n)

    def place(dev):
        return (arrays, n, dtu, dtv, dtval)

    def step(ctx, hi, lo, t):
        a, nn, qu, qv, qval = ctx
        su = jax.lax.dynamic_slice(qu, (0, t.start), (n_dev, cl))
        sv = jax.lax.dynamic_slice(qv, (0, t.start), (n_dev, cl))
        sva = jax.lax.dynamic_slice(qval, (0, t.start), (n_dev, cl))
        return plan._fn(a, nn, su, sv, sva, hi, lo)

    tasks = [ChunkTask(s, s + cl, float(cl * n_dev))
             for s in range(0, L, cl)]
    return plan.executor.run(tasks, place=place, step=step, init=init)


@spanned("delta_schedule")
def _pallas_subset_schedule(plan, g: CSRGraph, u: np.ndarray, v: np.ndarray):
    """Host-side (bucket, need) schedule for a pallas pass over the dyad
    sublist ``(u, v)`` — the subset mirror of the full pass's device sort,
    shared by the subset runner below and the partitioned drivers
    (:mod:`repro.engine.partition`), which must upload the device dyad
    list in the SAME order the task spans index into.

    Returns ``(u, v, tasks, chunk, block, interpret)`` with ``u``/``v``
    REORDERED into bucket-sorted order: every :class:`ChunkTask` carries
    the ``K`` specialization its span compiles against, so each dispatch
    hits an already-compiled tile kernel.  Runs inside the span
    ``repro.delta_schedule``."""
    cfg = plan.config
    interpret = cfg.resolve_interpret()
    block = cfg.resolve_block()
    chunk = max(block, (plan.chunk // block) * block)
    kmax = max(plan.meta.k, 1)
    ks = tuple(sorted({min(max(int(k), 1), kmax)
                       for k in cfg.buckets} | {kmax}))
    census_needed = "triad_census" in plan.layout.slices
    D = len(u)
    if census_needed and D:
        deg = np.asarray(g.arrays.nbr_deg)
        need = np.maximum(deg[u], deg[v]).astype(np.int64)
        probe = int(np.minimum(deg[u], deg[v]).sum(dtype=np.int64))
        ks_arr = np.asarray(ks, dtype=np.int64)
        b = (need[:, None] > ks_arr[None, :]).sum(1)
        order = np.lexsort((need, b))
        u, v, need, b = u[order], v[order], need[order], b[order]
        counts = np.bincount(b, minlength=len(ks))[: len(ks)]
        dynamic = cfg.schedule == "dynamic"
        if dynamic:
            cum = np.concatenate([[0.0], np.cumsum(need, dtype=np.float64)])
            target = cum[-1] / max(1, -(-D // chunk))
        tasks: list = []
        offset = 0
        for i, K in enumerate(ks):
            c = int(counts[i])
            if dynamic and c:
                bounds = offset + balance.chunk_bounds_by_cost(
                    need[offset:offset + c], chunk, target=target)
                tasks += [ChunkTask(int(a), int(e), float(cum[e] - cum[a]), K)
                          for a, e in zip(bounds[:-1], bounds[1:])]
            else:
                tasks += [ChunkTask(s, offset + c,
                                    float(K * min(chunk, offset + c - s)), K)
                          for s in range(offset, offset + c, chunk)]
            offset += c
        from .backends import count_tiles
        count_tiles(plan.stats, tasks, chunk, probe)
    else:
        tasks = [t._replace(key=kmax)
                 for t in _subset_tasks(plan, g, u, v, chunk)]
    return u, v, tasks, chunk, block, interpret


def _subset_pallas(plan, g: CSRGraph, u: np.ndarray, v: np.ndarray, *,
                   arrays=None, init=None, pad=None):
    """pallas subset pass: host-side (bucket, need) sort of the affected
    dyads mirrors the full pass's device sort, so every task dispatches an
    already-compiled ``K`` specialization of the tile kernel.

    ``arrays``/``init``/``pad`` as in :func:`_subset_xla` (the
    partitioned engine passes shard-local arrays: a kept row's
    direction-coded entries are the global row's)."""
    from .backends import _once_device

    if g.n_dyads == 0:
        return _zeros(plan) if init is None else init
    if arrays is None:
        arrays = plan.padded_arrays(g)
    n = jnp.int32(g.n)
    if init is None:
        init = _once_device(plan, *_zeros(plan), arrays, n)
    u, v, tasks, chunk, block, interpret = _pallas_subset_schedule(
        plan, g, u, v)
    stream_u, stream_v = _pad_dyad_list(plan, u, v, pad)

    def place(dev):
        ctx = (arrays, n, stream_u, stream_v)
        return ctx if dev is None else jax.device_put(ctx, dev)

    def step(ctx, hi, lo, t):
        a, nn, su, sv = ctx
        return plan._fn(a, nn, su, sv, jnp.int32(t.start), jnp.int32(t.end),
                        hi, lo, K=int(t.key), chunk=chunk, block=block,
                        interpret=interpret)

    return plan.executor.run(tasks, place=place, step=step, init=init)


_SUBSET_RUNNERS = {"xla": _subset_xla, "distributed": _subset_distributed,
                   "pallas": _subset_pallas}


def delta_correction(plan, g_old: CSRGraph, g_new: CSRGraph,
                     delta: GraphDelta, *,
                     affected_old=None, affected_new=None) -> np.ndarray:
    """Exact per-bin correction ``raw(g_new) - raw(g_old)`` for a plan's
    fused accumulator, via two affected-subset passes (see the module
    docstring).  Costs exactly ONE counted device→host sync.  Both graphs
    must pass the plan's admission check and the plan must be on the
    device-resident path (``Plan.apply_delta`` enforces both and falls
    back to a full recompute otherwise).

    ``affected_old`` / ``affected_new`` accept precomputed
    :func:`~repro.core.delta.affected_dyads` pairs so the caller's
    footprint measurement isn't recomputed.  Adds both passes' affected
    dyads to ``stats["delta_affected"]`` and the chunks they dispatch to
    ``stats["delta_chunks"]``; the difference and its fetch run inside
    the span ``repro.delta_fold``."""
    ou, ov = (affected_dyads(g_old, delta) if affected_old is None
              else affected_old)
    nu, nv = (affected_dyads(g_new, delta) if affected_new is None
              else affected_new)
    if plan.partitions > 1:
        # partitioned plans correct through the sharded subset pass: the
        # affected dyads group by owner shard and ONLY the owning shards'
        # local CSRs are rebuilt and dispatched — a delta touches the
        # shards holding its endpoints' ranges, not the whole graph.
        from .partition import subset_partitioned as runner
    else:
        runner = _SUBSET_RUNNERS[plan.backend]
    chunks = plan.stats["chunks"]
    hi_o, lo_o = runner(plan, g_old, ou, ov)
    hi_n, lo_n = runner(plan, g_new, nu, nv)
    plan.stats["delta_chunks"] += plan.stats["chunks"] - chunks
    plan.stats["delta_affected"] += len(ou) + len(nu)
    with span("delta_fold"):
        hi, lo = _acc_diff(hi_n, lo_n, hi_o, lo_o)
        return _acc_fetch(plan, hi, lo)


def run_delta(plan, g: CSRGraph, delta: GraphDelta,
              raw: "np.ndarray | None") -> DeltaResult:
    """The :meth:`repro.engine.Plan.apply_delta` implementation.

    Chooses between the affected-subset correction and a full recompute
    (``raw`` missing, footprint above ``config.delta_threshold``, the
    synchronous baseline path, or any op that opts out of the locality
    contract via ``delta_local=False``), applies it, and bumps the plan's
    ``delta_runs`` / ``delta_fulls`` counters.

    Deltas stay in ORIGINAL vertex ids under ``config.reorder``: the
    translation happens here, at the boundary.  The plan's memoized
    permutation relabels the delta (:meth:`GraphDelta.permuted`) and both
    subset passes run in relabeled space — ``apply_delta_csr`` commutes
    with relabeling because ``from_edges`` is canonical over arc sets, so
    the relabeled new graph IS the relabeling of the new graph (seeded
    into the reorder memo: a mutation stream reuses one permutation and
    every step stays warm).  The correction maps back through the inverse
    permutation before folding — exact, because ``unpermute`` is linear.

    Runs inside the span ``repro.delta``, whose args ``affected_old`` /
    ``affected_new`` are the two affected-dyad counts."""
    with span("delta") as sp:
        return _run_delta(plan, g, delta, raw, sp)


def _run_delta(plan, g: CSRGraph, delta: GraphDelta, raw, sp) -> DeltaResult:
    g_new = apply_delta_csr(g, delta)
    plan._check(g_new)
    fplan = resolve_faults(plan.config.fault_plan)
    if fplan is not None:
        # injected mid-mutate failure: the new graph exists but no counts
        # have been committed — stateful callers (the serve layer's
        # subscribed sessions) must roll back to their pre-mutation
        # (graph, raw) snapshot.  Keyed on a monotone per-plan attempt
        # counter (NOT the completed-run counters, which a failed attempt
        # never advances), so which application fails is deterministic
        # and a retry of a failed ordinal proceeds.
        ordinal = plan.stats.get("delta_attempts", 0)
        plan.stats["delta_attempts"] = ordinal + 1
        if fplan.mutate_fails(ordinal):
            raise InjectedFault(
                f"injected mid-mutate failure (delta application "
                f"#{ordinal})")
    if delta.is_empty:
        # nothing can change: zero-cost, no device work, no sync.  (The
        # raw bins are still required — an empty delta is not a run.)
        if raw is None:
            raw = plan._execute_raw(g_new)
            plan.stats["delta_fulls"] += 1
            return DeltaResult(g_new, raw, plan.layout.finalize(raw, g_new),
                               "full", 0.0)
        plan.stats["delta_runs"] += 1
        return DeltaResult(g_new, raw, plan.layout.finalize(raw, g_new),
                           "delta", 0.0)
    # reorder boundary: translate the mutation into the plan's execution
    # (relabeled) vertex space and seed the mutated graph's memo entry.
    g_x, perm = plan._reordered(g)
    if perm is not None:
        delta_x = delta.permuted(perm)
        g_new_x = apply_delta_csr(g_x, delta_x)
        plan._seed_reorder(g_new, g_new_x, perm)
    else:
        delta_x, g_new_x = delta, g_new
    affected_old = affected_dyads(g_x, delta_x)
    affected_new = affected_dyads(g_new_x, delta_x)
    sp.set_metadata(affected_old=len(affected_old[0]),
                    affected_new=len(affected_new[0]))
    frac = affected_fraction(g_x, g_new_x, len(affected_old[0]),
                             len(affected_new[0]))
    use_delta = (raw is not None and plan.device_path
                 and frac <= plan.config.delta_threshold
                 and all(getattr(op, "delta_local", True)
                         for op in plan.ops))
    if use_delta:
        corr = delta_correction(plan, g_x, g_new_x, delta_x,
                                affected_old=affected_old,
                                affected_new=affected_new)
        if perm is not None:
            corr = plan.layout.unpermute(corr, perm, g_new)
        raw_new = np.asarray(raw, dtype=np.int64) + corr
        plan.stats["delta_runs"] += 1
        mode = "delta"
    else:
        raw_new = plan._execute_raw(g_new)
        plan.stats["delta_fulls"] += 1
        mode = "full"
    return DeltaResult(g_new, raw_new, plan.layout.finalize(raw_new, g_new),
                       mode, frac)
