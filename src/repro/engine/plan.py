"""Compiled multi-analytic plans + the plan cache (the serving hot path).

``compile(graph_meta, ops, config) -> Plan`` is the engine's front door: a
:class:`Plan` owns everything the historical paths re-derived per call —
canonical-dyad enumeration, padding, tile building, degree bucketing, task
sharding, the scan/partial-histogram schedule — and executes **any number
of** :class:`~repro.engine.ops.GraphOp` analytics **in one fused pass**
over the streaming dyad pipeline: one traversal, one on-device hi/lo
accumulator (each op owns a slice), one device→host transfer, per-op
results.  Two properties carry over from the census-only engine:

  * a **plan cache** keyed on static graph metadata buckets (n, max-degree
    and arc counts rounded to powers of two) + op names + config, so
    repeated analytics on same-shape graphs reuse one compiled plan and
    hit zero retraces (bounded LRU — see :func:`set_plan_cache_capacity`),
  * **chunked streaming execution**: the compiled unit processes a
    fixed-shape chunk of dyads, so its trace is independent of the dyad
    count and graphs whose full dyad tiles exceed device memory still run.

``compile_census`` / :class:`CensusPlan` are the original census-only API,
now thin views over the same plans: a census wrapper and a new-API plan
for the same (bucket, config, ops) share ONE cache entry and one set of
compiled units — no double compiles.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import warnings
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.census import CensusResult
from ..core.graph import MAX_CODED_VERTICES, CSRGraph, GraphArrays
from ..core.graph import next_pow2 as _next_pow2
from ..core.reorder import compute_permutation, permute_graph
from ..core.spans import span
from . import backends
from .config import EngineConfig
from .executor import Executor
from .faults import InjectedFault, check_poisoned, resolve_faults
from .ops import OpLayout, resolve_ops

__all__ = ["CensusPlan", "GraphMeta", "Plan", "PlanShapeError", "compile",
           "compile_census", "clear_plan_cache", "plan_cache_stats",
           "set_plan_cache_capacity"]


class PlanShapeError(ValueError):
    """A graph exceeds the plan's metadata buckets (tile width or array
    bounds) — recompile via :func:`repro.engine.compile` at the graph's
    own shape.  Subclasses ``ValueError`` so pre-existing handlers keep
    working; exists as its own type so stateful callers (the serve
    layer's subscribed sessions) can tell "this graph outgrew its plan,
    recompile" apart from genuinely invalid input."""


@dataclasses.dataclass(frozen=True)
class GraphMeta:
    """Static, bucketized graph shape — the graph half of the plan-cache
    key.

    All fields are rounded up to powers of two so graphs of similar shape
    map to the same plan (and therefore the same compiled trace).
    """

    n_bucket: int       # vertices, rounded up
    k: int              # candidate tile width (>= max undirected degree)
    member_iters: int   # binary-search trips covering any CSR row
    m_out_bucket: int   # directed-arc array length, rounded up
    m_nbr_bucket: int   # undirected-adjacency array length, rounded up

    @classmethod
    def from_graph(cls, g: CSRGraph, k: Optional[int] = None) -> "GraphMeta":
        k_bucket = _next_pow2(max(g.max_deg, 1))
        k_eff = int(k) if k else k_bucket
        # membership searches run over REAL rows, so iteration count must
        # cover the true max degree even under a (dryrun) K override.
        depth = max(k_eff, k_bucket)
        iters = max(1, math.ceil(math.log2(depth + 1))) + 1
        return cls(
            n_bucket=_next_pow2(max(g.n, 1)),
            k=k_eff,
            member_iters=iters,
            m_out_bucket=_next_pow2(max(g.m, 1)),
            m_nbr_bucket=_next_pow2(max(g.m_nbr, 1)),
        )


def _pad_to(a: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full(size, fill, dtype=a.dtype)
    out[: len(a)] = a
    return out


class Plan:
    """A compiled, reusable fused-analytic execution plan.

    Create via :func:`compile`; run with :meth:`run` (returns ``{op_name:
    result}``).  One plan serves every graph whose :class:`GraphMeta`
    matches — arrays are padded to the metadata buckets before entering
    the device, so no input shape (and hence no trace) depends on the
    concrete graph.  However many ops the plan carries, execution is one
    traversal of the dyad stream and one device→host transfer
    (``stats["host_syncs"]`` is identical to a single-op run).
    """

    def __init__(self, meta: GraphMeta, ops, config: EngineConfig,
                 backend: str, mesh=None):
        self.meta = meta
        self.ops = tuple(ops)
        self.op_names = tuple(op.name for op in self.ops)
        self.config = config
        self.backend = backend
        self.mesh = mesh
        self.layout = OpLayout(self.ops, meta, config)
        # streaming chunk, capped by the graph's dyad-count bucket
        # (m_nbr_bucket/2 >= n_dyads) so small graphs don't pad to a full
        # default chunk; both terms are static, so shapes stay cache-stable.
        batch = config.batch
        dyad_cap = -(-max(1, meta.m_nbr_bucket // 2) // batch) * batch
        self.chunk = min(config.resolve_chunk(), dyad_cap)
        # device-resident dyad list length: the dyad-count bucket rounded up
        # to whole chunks, so every chunk's dynamic_slice stays in bounds
        # (and the shape stays a pure function of the metadata buckets).
        d_bucket = max(1, meta.m_nbr_bucket // 2)
        self.dyad_pad = max(self.chunk, -(-d_bucket // self.chunk) * self.chunk)
        self.device_path = config.resolve_device_accum()
        # partitioned-graph subsystem: shard count (1 = unpartitioned) and
        # the locality precondition — every op's per-dyad contribution must
        # read only {u, v} ∪ N(u) ∪ N(v) (the delta_local contract), which
        # is exactly what each shard's halo keeps locally.
        self.partitions = config.resolve_partitions()
        self.partition_mode = config.resolve_partition_mode(backend)
        if self.partitions > 1:
            nonlocal_ops = [op.name for op in self.ops
                            if not getattr(op, "delta_local", True)]
            if nonlocal_ops:
                raise ValueError(
                    f"partitions={self.partitions} requires every op to "
                    f"honor the delta_local locality contract, but "
                    f"{nonlocal_ops} opt out — their kernels may read "
                    "rows outside a shard's halo; run them unpartitioned "
                    "(partitions=1)")
            if self.partition_mode == "mesh" and backend != "distributed":
                raise ValueError(
                    f"partition_mode='mesh' requires the distributed "
                    f"backend (got backend={backend!r}): the mesh mode "
                    "stacks shard contexts along a shard_map mesh axis — "
                    "use partition_mode='pool' (concurrent pool devices) "
                    "or 'serial' on this backend")
            if self.partition_mode == "pool" and backend == "distributed":
                raise ValueError(
                    "partition_mode='pool' is not available on the "
                    "distributed backend: its mesh already owns every "
                    "device (the executor pool is pinned to one slot) — "
                    "use partition_mode='mesh' (the default there) or "
                    "'serial'")
        self.stats = {"traces": 0, "runs": 0, "chunks": 0, "host_syncs": 0,
                      "batch_runs": 0, "batch_graphs": 0, "device_chunks": {},
                      "delta_runs": 0, "delta_fulls": 0,
                      "delta_affected": 0, "delta_chunks": 0, "reorders": 0,
                      "tile_slots": 0, "gather_blocks": 0, "dyads": 0,
                      "probe_columns": 0, "bytes_staged": 0,
                      "task_memo_hits": 0, "task_memo_misses": 0,
                      "faults": dict(chunk_failures=0, retries=0,
                                     device_losses=0, quarantines=0,
                                     backend_fallbacks=0,
                                     schedule_fallbacks=0),
                      "fault_events": []}
        # the degradation ladder: `backend` is the rung currently
        # executing, `requested_backend` the compile-time ask, and
        # `degradation` the (usually empty) record of every demotion —
        # surfaced per cache entry by plan_cache_stats().
        self.requested_backend = backend
        self.degradation: list = []
        # chunk dispatch policy + device pool (static 1-slot by default;
        # the distributed backend's mesh already owns every device, so its
        # pool is always pinned to one slot).
        self.executor = Executor(
            config, self.stats,
            n_devices=(1 if backend == "distributed"
                       else config.resolve_executor_devices()),
            backend=backend)
        self._batch_fn = None  # lazily-built vmapped unit (xla device path)
        self._census_view = None  # memoized CensusPlan compat wrapper
        # bounded per-graph memo of host-derived chunk schedules
        # (see repro.engine.backends._memo_tasks)
        self._task_memo: dict = {}
        # bounded per-graph memo of reorder permutations + relabeled
        # graphs (config.reorder != "none"): warm runs pay zero reorder
        # cost.  Same lifetime/bound discipline as _task_memo.
        self._reorder_memo: dict = {}
        # bounded per-graph memo of partition layouts (metadata only —
        # cuts, halo ids, shard sizes; local CSRs rebuild per run).  Same
        # lifetime/bound discipline as the memos above.
        self._partition_memo: dict = {}
        # lazily-built shard_map unit for partition_mode="mesh" (one per
        # plan; jit retraces per shard-geometry bucket like every unit).
        self._mesh_part_fn = None
        # distributed: per-shard load summary of the most recent run
        # (a backends.TaskStats — plans are cached with a bounded LRU, so
        # only the (n_shards,) weights are retained, never the task arrays).
        self.last_task_stats = None
        fplan = resolve_faults(config.fault_plan)
        try:
            if fplan is not None and fplan.compile_fails(backend):
                raise InjectedFault(f"injected {backend} compile failure")
            if backend == "pallas" and meta.n_bucket > MAX_CODED_VERTICES:
                raise ValueError(
                    f"the pallas census kernel packs vertex ids into "
                    f"direction-coded rows (nbr_code), exact for at most "
                    f"{MAX_CODED_VERTICES} vertices; this plan's vertex "
                    f"bucket is {meta.n_bucket}")
            self._fn = self._build_fn(backend)
        except Exception as e:
            # pallas→xla is the only compile-fallback rung: the xla unit
            # runs the same fused layout anywhere, while a distributed
            # mesh failure or an unknown backend has no safe substitute.
            if backend != "pallas" or not config.backend_fallback:
                raise
            self._demote("xla", stage="compile", reason=repr(e))

    def _build_fn(self, backend: str):
        """Build ``backend``'s compiled chunk/stream unit (the ladder
        re-enters this when demoting pallas→xla)."""
        config = self.config
        if backend == "xla":
            return (
                backends.make_xla_stream_fn(self.layout, config, self.stats,
                                            self.chunk)
                if self.device_path
                else backends.make_xla_chunk_fn(self.layout, config,
                                                self.stats))
        if backend == "distributed":
            if self.mesh is None:
                raise ValueError("distributed backend needs a mesh")
            make = (backends.make_distributed_stream_fn if self.device_path
                    else backends.make_distributed_chunk_fn)
            return make(self.layout, config, self.mesh, self.stats)
        if backend == "pallas":
            # fused chunk unit; pallas_call manages its own per-shape cache
            return backends.make_pallas_chunk_fn(self.layout, config,
                                                 self.stats)
        raise ValueError(f"unknown backend {backend!r}")

    def _demote(self, to: str, *, stage: str, reason: str) -> None:
        """One rung of the degradation ladder: permanently re-point this
        plan at backend ``to`` (rebuilding its compiled unit), record the
        event in ``degradation`` / ``stats``, warn with the reason, and
        keep serving.  The xla unit computes the same fused integer bins,
        so demoted results stay bit-identical; chunk-schedule memo entries
        are keyed by backend kind and cannot leak across the demotion."""
        frm = self.backend
        warnings.warn(f"{frm} backend demoted to {to} at {stage}: {reason}",
                      RuntimeWarning, stacklevel=3)
        self.backend = to
        self.executor.backend = to
        self._fn = self._build_fn(to)
        self._batch_fn = None
        self.stats["faults"]["backend_fallbacks"] += 1
        trace = self.stats["fault_events"]
        if len(trace) < 512:
            trace.append(("backend_fallback", frm, to, stage))
        self.degradation.append(dict(rung=f"{frm}->{to}", stage=stage,
                                     reason=reason))

    # -- graph admission -----------------------------------------------------

    def _check(self, g: CSRGraph):
        m = self.meta
        if g.max_deg > m.k:
            raise PlanShapeError(
                f"graph max_deg={g.max_deg} exceeds plan tile width k={m.k}; "
                f"recompile via repro.engine.compile(graph, ops, config)")
        if g.n > m.n_bucket or g.m > m.m_out_bucket or g.m_nbr > m.m_nbr_bucket:
            raise PlanShapeError(
                f"graph (n={g.n}, m={g.m}, m_nbr={g.m_nbr}) exceeds plan "
                f"buckets {m}; recompile via repro.engine.compile(graph, "
                f"ops, config)")

    def padded_arrays_host(self, g: CSRGraph) -> GraphArrays:
        """Bucket-padded arrays as host numpy (no device transfer).

        The batched path (:func:`repro.engine.backends.run_xla_batch`)
        pads + stacks a whole batch on host and ships **one** device put
        per field — per-graph puts would otherwise dominate small-graph
        fleet serving.  Padding semantics match :meth:`padded_arrays`.
        """
        m = self.meta
        a = g.arrays
        out_ptr = np.asarray(a.out_ptr)
        nbr_ptr = np.asarray(a.nbr_ptr)
        return GraphArrays(
            out_ptr=_pad_to(out_ptr, m.n_bucket + 1, out_ptr[-1]),
            out_idx=_pad_to(np.asarray(a.out_idx), m.m_out_bucket, 0),
            nbr_ptr=_pad_to(nbr_ptr, m.n_bucket + 1, nbr_ptr[-1]),
            nbr_idx=_pad_to(np.asarray(a.nbr_idx), m.m_nbr_bucket, 0),
            nbr_deg=_pad_to(np.asarray(a.nbr_deg), m.n_bucket, 0),
            nbr_code=_pad_to(np.asarray(a.nbr_code), m.m_nbr_bucket, 0),
        )

    def padded_arrays(self, g: CSRGraph) -> GraphArrays:
        """Device arrays padded to the metadata buckets (shape-stable).

        Padded ptr rows repeat the last offset (empty rows: binary search
        sees lo == hi and never matches); padded idx/deg/code entries are
        inert.
        """
        with span("stage"):
            host = self.padded_arrays_host(g)
            self.stats["bytes_staged"] += sum(v.nbytes for v in host)
            arrays = GraphArrays(*(jnp.asarray(v) for v in host))
        return arrays

    # -- locality-aware reordering -------------------------------------------

    def _seed_reorder(self, g: CSRGraph, g_exec: CSRGraph,
                      perm: np.ndarray) -> None:
        """Record ``(g -> (g_exec, perm))`` in the bounded reorder memo.

        Keyed by graph identity with a weakref guard against id reuse
        (the ``_memo_tasks`` discipline); bounded to 8 live graphs per
        plan — mutation streams touch one or two.  The delta path seeds
        the mutated graph's entry so a session's every step reuses ONE
        permutation and stays warm.
        """
        memo = self._reorder_memo
        while len(memo) >= 8:
            memo.pop(next(iter(memo)))
        memo[id(g)] = (weakref.ref(g), g_exec, perm)

    def _reordered(self, g: CSRGraph):
        """``(execution graph, perm)`` for this plan's ``reorder=`` policy.

        ``("none")`` returns ``(g, None)`` — the zero-cost identity.
        Otherwise the permutation (``perm[old_id] = new_id``, see
        :mod:`repro.core.reorder`) is computed host-side ONCE per (plan,
        graph) and memoized together with the relabeled graph; warm runs
        pay nothing (``stats["reorders"]`` counts the cold computations).
        Relabeling preserves every metadata bucket, so the execution
        graph passes the same admission check the original did.
        """
        if self.config.reorder == "none":
            return g, None
        hit = self._reorder_memo.get(id(g))
        if hit is not None and hit[0]() is g:
            return hit[1], hit[2]
        perm = compute_permutation(g, self.config.reorder)
        g_exec = permute_graph(g, perm)
        self.stats["reorders"] += 1
        self._seed_reorder(g, g_exec, perm)
        return g_exec, perm

    def _execute_raw(self, g: CSRGraph) -> np.ndarray:
        """Reorder-aware raw execution: relabel (memoized), dispatch the
        backend on the execution graph, and map raw bins back through the
        inverse permutation (identity for aggregate ops — see
        ``GraphOp.unpermute_raw``), so the raw contract is always
        ORIGINAL vertex space regardless of ``config.reorder``."""
        g_exec, perm = self._reordered(g)
        raw = self._run_raw(g_exec)
        return raw if perm is None else self.layout.unpermute(raw, perm, g)

    # -- execution -----------------------------------------------------------

    def run(self, g: CSRGraph) -> dict:
        """Execute every op in one fused pass; returns ``{op_name: result}``.

        One traversal of the dyad stream, one on-device accumulator, one
        device→host sync — the same schedule a single-op plan runs.
        Semantically the ``B = 1`` case of :meth:`run_batch`; it executes
        through the single-graph (un-vmapped) units, which produce
        bit-identical raw bins — every op is pure integer arithmetic.
        """
        raw = self.run_raw(g)
        with span("finalize"):
            return self.layout.finalize(raw, g)

    def run_raw(self, g: CSRGraph) -> np.ndarray:
        """Execute the fused pass and return the raw int64 accumulator bins
        (no per-op finalize).  This is the state a delta-census stream
        carries between mutations: seed a session with ``raw =
        plan.run_raw(g)``, then advance it with :meth:`apply_delta` —
        ``layout.finalize(raw, g)`` recovers the per-op results at any
        point.  Counts as one run (same stats/sync accounting as
        :meth:`run`).  Raw bins are always in ORIGINAL vertex space: under
        ``config.reorder`` the backend runs on the relabeled graph and the
        bins map back through the inverse permutation before returning."""
        check_poisoned(g)
        self._check(g)
        self.stats["runs"] += 1
        with span("run", run=self.stats["runs"]):
            return self._execute_raw(g)

    def apply_delta(self, g: CSRGraph, delta, raw=None) -> "DeltaResult":
        """Advance a census stream by one mutation batch — work
        proportional to the delta's footprint, not the graph.

        ``g`` is the current graph and ``raw`` its raw bins (from
        :meth:`run_raw` or the previous application's ``.raw``); ``delta``
        is a :class:`~repro.core.delta.GraphDelta`.  Returns a
        :class:`~repro.engine.delta.DeltaResult` whose ``graph`` / ``raw``
        seed the next application and whose ``results`` are bit-identical
        to ``plan.run(result.graph)`` — the correction pass re-runs the
        plan's own chunk machinery on the affected dyads of both graphs
        and folds the exact integer difference (module
        :mod:`repro.engine.delta`), costing ONE counted device→host sync.
        Falls back to a full recompute (``mode == "full"``) when ``raw``
        is ``None``, the affected fraction exceeds
        ``config.delta_threshold``, the plan runs the synchronous
        baseline, or an op opts out via ``delta_local=False``.  Raises
        :class:`PlanShapeError` if the mutated graph outgrows the plan's
        buckets — recompile at the new shape and rerun.
        """
        from .delta import run_delta
        self._check(g)
        self.stats["runs"] += 1
        with span("run", run=self.stats["runs"]):
            return run_delta(self, g, delta, raw)

    def _run_raw(self, g: CSRGraph) -> np.ndarray:
        """Backend dispatch: the fused raw int64 bins (no finalize).

        The pallas→xla runtime rung of the degradation ladder lives
        here: a pallas run that fails (after the executor's own bounded
        retries) demotes the plan and re-runs on xla — bit-identical
        bins, one extra counted sync for the failed run only, and every
        later run executes on the demoted rung directly.

        ``partitions > 1`` dispatches the sharded-CSR path instead
        (:func:`repro.engine.partition.run_partitioned`) — inside the
        same try, so the ladder composes: a failed pallas shard pass
        demotes the plan and the whole partitioned run re-enters on
        xla.  Reordering composes upstream (``_execute_raw`` relabels
        before dispatch), so partition cuts are computed over the
        locality-relabeled ids — PR 8's reorder doubles as the
        partitioner."""
        try:
            if self.partitions > 1:
                from .partition import run_partitioned
                return run_partitioned(self, g)
            return backends.RUNNERS[self.backend](self, g)
        except Exception as e:
            if self.backend != "pallas" or not self.config.backend_fallback:
                raise
            self._demote("xla", stage="runtime", reason=repr(e))
            return self._run_raw(g)

    def run_batch(self, graphs) -> "list[dict]":
        """Execute the fused pass on B same-bucket graphs as one batch.

        Every graph must pass this plan's admission check (same metadata
        buckets — the :class:`GraphMeta` grouping a
        :class:`repro.serve.CensusService` performs).  On the xla
        device-resident path the whole batch runs through one vmapped
        fixed-shape unit — a leading batch axis over the padded graph
        arrays, the device dyad lists and the fused hi/lo accumulator —
        so B requests cost one chunk schedule of dispatches and **one**
        device→host transfer instead of B of each.  Results are
        bit-identical to B sequential :meth:`run` calls (integer
        arithmetic; excess chunks for shorter graphs are masked no-ops).

        The pallas / distributed backends and the synchronous baseline
        (``device_accum=False``) have no vmapped unit yet; there the batch
        executes member-wise through the single-graph path — same results,
        amortizing only the plan, not the dispatch.

        Returns one ``{op_name: result}`` dict per graph, in input order.
        """
        graphs = list(graphs)
        if not graphs:
            return []
        for g in graphs:
            # a poisoned member fails the batch as a unit — the serve
            # layer's member-wise retry is what isolates it from peers.
            check_poisoned(g)
            self._check(g)
        self.stats["runs"] += len(graphs)
        self.stats["batch_runs"] += 1
        self.stats["batch_graphs"] += len(graphs)
        with span("run", run=self.stats["runs"]):
            if (self.backend == "xla" and self.device_path
                    and self.partitions == 1):
                # reorder each member (memoized) and batch the relabeled
                # graphs — same buckets, so the vmapped unit is unchanged;
                # raw bins map back per member before finalize.
                # Partitioned plans take the member-wise branch below:
                # each member runs the sharded path with its own bounded
                # shard contexts.
                pairs = [self._reordered(g) for g in graphs]
                raws = backends.run_xla_batch(self, [ge for ge, _ in pairs])
                return [self.layout.finalize(
                            raw if perm is None
                            else self.layout.unpermute(raw, perm, g), g)
                        for raw, (_, perm), g in zip(raws, pairs, graphs)]
            return [self.layout.finalize(self._execute_raw(g), g)
                    for g in graphs]

    def batch_fn(self):
        """The vmapped batched unit (xla device path), built lazily.

        One jitted callable serves every batch size — jit retraces per
        distinct (power-of-two-padded) B, counted in ``stats['traces']``.
        """
        if self._batch_fn is None:
            self._batch_fn = backends.make_xla_stream_batch_fn(
                self.layout, self.config, self.stats, self.chunk)
        return self._batch_fn

    def aot_lower(self, g: CSRGraph):
        """Lower the compiled chunk unit at this plan's static shapes.

        For dry-run/roofline analysis (memory_analysis, cost_analysis)
        without executing.  Only xla/distributed expose a jitted unit.
        """
        if self.backend == "pallas":
            raise NotImplementedError("pallas backend has no jitted unit")
        m = self.meta
        arrays = GraphArrays(
            out_ptr=jax.ShapeDtypeStruct((m.n_bucket + 1,), jnp.int32),
            out_idx=jax.ShapeDtypeStruct((m.m_out_bucket,), jnp.int32),
            nbr_ptr=jax.ShapeDtypeStruct((m.n_bucket + 1,), jnp.int32),
            nbr_idx=jax.ShapeDtypeStruct((m.m_nbr_bucket,), jnp.int32),
            nbr_deg=jax.ShapeDtypeStruct((m.n_bucket,), jnp.int32),
            nbr_code=jax.ShapeDtypeStruct((m.m_nbr_bucket,), jnp.int32),
        )
        n = jax.ShapeDtypeStruct((), jnp.int32)
        if self.backend == "distributed":
            n_dev = math.prod(self.mesh.devices.shape)
            shape = (n_dev, backends.chunk_l(self))
        else:
            shape = (self.chunk,)
        ints = jax.ShapeDtypeStruct(shape, jnp.int32)
        bools = jax.ShapeDtypeStruct(shape, jnp.bool_)
        if not self.device_path:
            return self._fn.lower(arrays, n, ints, ints, bools)
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        acc = jax.ShapeDtypeStruct((self.layout.total_bins,), jnp.int32)
        if self.backend == "distributed":
            return self._fn.lower(arrays, n, ints, ints, bools, acc, acc)
        dyads = jax.ShapeDtypeStruct((self.dyad_pad,), jnp.int32)
        return self._fn.lower(arrays, n, dyads, dyads, scalar, scalar,
                              acc, acc)

    # -- compat --------------------------------------------------------------

    def census_view(self) -> "CensusPlan":
        """The census-only compat view over this plan (memoized — repeat
        calls return the identical :class:`CensusPlan` object, which is
        what keeps ``compile_census``'s is-identity cache semantics)."""
        if "triad_census" not in self.op_names:
            raise ValueError(f"plan ops {self.op_names} do not include "
                             "'triad_census'")
        if self._census_view is None:
            self._census_view = CensusPlan(self)
        return self._census_view


class CensusPlan:
    """Triad-census view of a generalized :class:`Plan` (the original
    census-only API, unchanged for callers).

    Created by ``compile_census``; every attribute (``stats``, ``meta``,
    ``config``, ``chunk``, ``device_path``, ...) delegates to the
    underlying multi-op plan — the SAME cached object a new-API
    ``compile(graph, ("triad_census",), config)`` returns — and
    :meth:`run` / :meth:`run_batch` unwrap the fused result dict to bare
    :class:`~repro.core.census.CensusResult` values, bit-identical to the
    pre-GraphOp engine.
    """

    def __init__(self, plan: Plan):
        self._plan = plan

    def __getattr__(self, name):
        return getattr(self._plan, name)

    def run(self, g: CSRGraph) -> CensusResult:
        """Execute the census; returns int64 counts for all 16 triad types
        (including the type-003 closed form).  Semantically the ``B = 1``
        case of :meth:`run_batch` — see :meth:`Plan.run`.
        """
        return self._plan.run(g)["triad_census"]

    def run_batch(self, graphs) -> "list[CensusResult]":
        """Execute the census on B same-bucket graphs as one batch.

        The census-only unwrapping of :meth:`Plan.run_batch` (see there
        for batching semantics): one vmapped dispatch schedule and one
        device→host transfer on the xla device path, member-wise fallback
        elsewhere, results bit-identical to sequential :meth:`run` calls.
        Returns one :class:`~repro.core.census.CensusResult` per graph,
        in input order.
        """
        return [r["triad_census"] for r in self._plan.run_batch(graphs)]

    def padded_arrays(self, g: CSRGraph) -> GraphArrays:
        """Device arrays padded to the metadata buckets (shape-stable);
        see :meth:`Plan.padded_arrays` for padding semantics."""
        return self._plan.padded_arrays(g)

    def padded_arrays_host(self, g: CSRGraph) -> GraphArrays:
        """Bucket-padded arrays as host numpy (no device transfer); see
        :meth:`Plan.padded_arrays_host` for why the batched path wants
        host-side padding."""
        return self._plan.padded_arrays_host(g)

    def aot_lower(self, g: CSRGraph):
        """Lower the compiled chunk unit at this plan's static shapes for
        dry-run/roofline analysis; see :meth:`Plan.aot_lower`."""
        return self._plan.aot_lower(g)

    def batch_fn(self):
        """The vmapped batched unit (xla device path), built lazily on the
        underlying plan; see :meth:`Plan.batch_fn`."""
        return self._plan.batch_fn()


# ----------------------------------------------------------------------------
# plan cache (bounded LRU)
# ----------------------------------------------------------------------------

_PLAN_CACHE: collections.OrderedDict = collections.OrderedDict()
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_DEFAULT_CAPACITY = 32
_CACHE_CAPACITY = _DEFAULT_CAPACITY


def set_plan_cache_capacity(capacity: int) -> None:
    """Bound the plan cache to ``capacity`` entries (LRU eviction).

    Long-lived multi-graph services would otherwise accumulate one
    compiled plan (and its XLA executable) per distinct metadata bucket
    forever.  Shrinking the capacity evicts the least-recently-used plans
    immediately; evictions are counted in :func:`plan_cache_stats`.
    """
    global _CACHE_CAPACITY
    if capacity < 1:
        raise ValueError("plan cache capacity must be >= 1")
    _CACHE_CAPACITY = capacity
    _evict_to_capacity()


def _evict_to_capacity() -> None:
    while len(_PLAN_CACHE) > _CACHE_CAPACITY:
        _PLAN_CACHE.popitem(last=False)
        _CACHE_STATS["evictions"] += 1


@functools.lru_cache(maxsize=8)
def _default_mesh(n_dev: int):
    return jax.make_mesh((n_dev,), ("data",))


def compile(graph_meta, ops=("triad_census",),
            config: Optional[EngineConfig] = None, *, mesh=None) -> Plan:
    """Build (or fetch from cache) the fused plan for this graph shape +
    op set.

    ``graph_meta`` is a :class:`CSRGraph` (metadata extracted and
    bucketized) or an explicit :class:`GraphMeta`.  ``ops`` is a GraphOp
    name, a :class:`~repro.engine.ops.GraphOp` instance, or a sequence of
    either (see :func:`repro.engine.ops.list_ops`); order fixes the
    result-dict order.  Plans are cached on (metadata buckets, op names,
    config, resolved backend, mesh): a second compile for a same-shape
    graph returns the identical plan object and re-uses its compiled
    trace — and a census-only ``compile_census`` call shares the same
    entry as ``compile(graph, ("triad_census",), config)``.
    """
    with span("compile") as sp:
        config = config or EngineConfig()
        op_objs = resolve_ops(ops)
        meta = (graph_meta if isinstance(graph_meta, GraphMeta)
                else GraphMeta.from_graph(graph_meta, k=config.k))
        backend = config.resolve_backend()
        # normalize: an "auto" config and the explicit backend it resolves
        # to must share one cache entry (and one compiled plan); likewise
        # device_accum=None and the True it resolves to, and the executor
        # pool width None/over-asked resolves to (1 under the static
        # schedule and on the distributed backend, whose mesh owns every
        # device).
        config = dataclasses.replace(
            config, backend=backend,
            device_accum=config.resolve_device_accum(),
            n_executor_devices=(1 if backend == "distributed"
                                else config.resolve_executor_devices()),
            partitions=config.resolve_partitions(),
            spill=config.resolve_spill(),
            partition_mode=config.resolve_partition_mode(backend))
        if backend == "distributed" and mesh is None:
            mesh = _default_mesh(len(jax.devices()))
        # key on the op *instances* (identity), not their names:
        # re-registering an op (overwrite=True) or passing an unregistered
        # instance whose name collides with a built-in must compile fresh,
        # never reuse a plan built against a different implementation.
        key = (meta, op_objs, config, mesh)
        plan = _PLAN_CACHE.get(key)
        sp.set_metadata(hit=plan is not None)
        if plan is not None:
            _CACHE_STATS["hits"] += 1
            _PLAN_CACHE.move_to_end(key)  # LRU freshness
            return plan
        _CACHE_STATS["misses"] += 1
        plan = Plan(meta, op_objs, config, backend, mesh)
        _PLAN_CACHE[key] = plan
        _evict_to_capacity()
        return plan


def compile_census(graph_meta, config: Optional[EngineConfig] = None, *,
                   mesh=None) -> CensusPlan:
    """Build (or fetch from cache) the census plan for this graph shape.

    The original front door, now a thin wrapper: delegates to
    ``compile(graph_meta, ("triad_census",), config)`` — so census-only
    wrapper plans and new-API plans for the same (bucket, config, ops)
    share ONE cache entry and compile once — and returns the plan's
    memoized census view (repeat calls on a warm cache return the
    identical :class:`CensusPlan` object).
    """
    return compile(graph_meta, ("triad_census",), config,
                   mesh=mesh).census_view()


def clear_plan_cache() -> None:
    """Drop every cached plan and reset hit/miss/eviction counters.

    Compiled XLA executables owned by the dropped plans become garbage;
    use in tests/benchmarks to force cold compiles.  Each plan's
    per-graph chunk-schedule memo (``_task_memo`` — the host-derived
    pallas bucket schedules and cost-model boundaries) is cleared too,
    as is its reorder memo (``_reorder_memo`` — the per-graph locality
    permutations and relabeled graphs): both memos' lifetimes are tied to
    the plan cache, so long-lived mutation streams can drop every
    host-side schedule and permutation with one call.
    """
    for p in _PLAN_CACHE.values():
        p._task_memo.clear()
        p._reorder_memo.clear()
        p._partition_memo.clear()
    _PLAN_CACHE.clear()
    _CACHE_STATS.update(hits=0, misses=0, evictions=0)


def plan_cache_stats() -> dict:
    """Plan-cache counters plus per-entry (per-bucket) metadata.

    Returns ``hits`` / ``misses`` / ``evictions`` / ``size`` /
    ``capacity`` plus ``entries``: one dict per cached plan, in LRU order
    (oldest first), holding the bucketized ``meta`` fields, ``backend``
    (the rung currently executing) with ``requested_backend`` and the
    ``degradation`` event list (the ladder's per-plan record, normally
    empty), ``device_path``, the plan's ``ops`` (op-name tuple), the resolved
    streaming ``chunk``, the executor policy (``schedule`` and
    ``n_devices`` — the resolved pool width), and the plan's live
    execution counters (``runs``, ``batch_runs``, ``batch_graphs``,
    ``traces``, ``chunks``, ``host_syncs``, ``delta_runs`` /
    ``delta_fulls`` — incremental applications split by path — and
    ``delta_affected`` / ``delta_chunks`` — affected dyads over both
    subset passes and the chunks those passes dispatched — plus
    ``faults`` / ``fault_events``: the executor's recovery counters and
    bounded event trace (retries, quarantines, device losses,
    fallbacks), ``device_chunks``: chunks dispatched per executor pool
    device, and
    ``task_memo``: live entries in the plan's bounded per-graph
    chunk-schedule memo, cleared with the cache by
    :func:`clear_plan_cache`, and the locality policy — ``reorder``
    (the plan's relabeling strategy) with ``reorder_memo``, the live
    entries in its bounded per-graph permutation memo).  Partitioned
    plans additionally report ``partitions`` (the configured shard
    count; 1 = unpartitioned), ``partition_mode`` (the resolved shard
    residency policy — ``"pool"`` / ``"serial"`` / ``"mesh"``, ``None``
    unpartitioned), ``partition_memo`` (live layout-memo
    entries) and — after a partitioned run — ``partition``, the last
    run's layout record (cuts, per-shard dyad counts, halo sizes, spill
    staging footprint, plus the residency observables: ``h2d_puts``
    (counted host→device shard stagings), ``d2d_puts`` (device-side halo
    peer transfers), ``shard_overlap`` (fraction of busy wall time with
    two or more shards in flight) and ``shard_times`` (per-shard
    start/end/tasks/device records); see
    :mod:`repro.engine.partition`).  This is the introspection surface
    :class:`repro.serve.CensusService` reports per-bucket stats from.
    """
    entries = [
        dict(meta=dataclasses.asdict(p.meta), backend=p.backend,
             requested_backend=p.requested_backend,
             degradation=[dict(d) for d in p.degradation],
             device_path=p.device_path, chunk=p.chunk, ops=p.op_names,
             schedule=p.config.schedule, n_devices=p.executor.n_devices,
             task_memo=len(p._task_memo), reorder=p.config.reorder,
             reorder_memo=len(p._reorder_memo),
             partitions=p.partitions,
             partition_mode=p.partition_mode,
             partition_memo=len(p._partition_memo),
             **{**p.stats,
                "device_chunks": dict(p.stats["device_chunks"]),
                "faults": dict(p.stats["faults"]),
                "fault_events": list(p.stats["fault_events"]),
                **({"partition": dict(p.stats["partition"])}
                   if "partition" in p.stats else {})})
        for p in _PLAN_CACHE.values()
    ]
    return {**_CACHE_STATS, "size": len(_PLAN_CACHE),
            "capacity": _CACHE_CAPACITY, "entries": entries}
