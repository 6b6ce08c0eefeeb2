"""Engine configuration (the single front door's knob surface).

One frozen, hashable dataclass — :class:`EngineConfig` — covers every
execution knob for any set of :class:`~repro.engine.ops.GraphOp`
analytics: backend choice, batch/tile geometry, load balancing,
accumulator dtype, interpret mode, and the streaming chunk size.
:data:`CensusConfig` is the same class under its original census-era
name, kept so existing call sites (and pickles of the config) keep
working — aliasing rather than subclassing means wrapper-API and new-API
plans hash equal and share one plan-cache entry.  Hashability matters:
the config is one third of the plan-cache key (with the graph metadata
buckets and the op names).
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .faults import FaultPlan

BACKENDS = ("xla", "pallas", "distributed", "auto")
SCHEDULES = ("static", "dynamic")
REORDERS = ("none", "degree", "bfs", "rcm")
PARTITION_MODES = ("serial", "pool", "mesh")

_ACC_DTYPES = {"int32": jnp.int32, "int64": jnp.int64, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static execution policy for a fused graph-analytic pass.

    Attributes:
        backend: ``"xla"`` (binary-search scan), ``"pallas"`` (degree-bucketed
            VMEM tile kernel), ``"distributed"`` (shard_map SPMD), or
            ``"auto"`` (resolved from the visible hardware at compile time).
        batch: dyads per scan step (xla/distributed backends).
        block: pallas kernel block (dyads per grid step).  ``None`` picks
            ``min(batch, 32)`` — the kernel's VMEM holds six
            double-buffered ``(block, K)`` tiles plus three ``(block, K)``
            accumulators, about 15 MiB at ``(32, 8192)``.  On a TPU the
            block must be a multiple of 8.
        k: tile width override (candidate lanes per dyad).  ``None`` derives
            a power-of-two bucket from the graph's max degree so same-shape
            graphs share one compiled plan.
        buckets: degree-bucket tile widths for the pallas backend (the
            smallest bucket >= a dyad's degree need wins).  Validated at
            construction: non-empty, strictly increasing, all positive —
            an unsorted or non-positive bucket list used to fail silently
            deep in tile building.
        strategy / weight_model: task packing for the distributed backend
            (see :mod:`repro.core.balance`).
        acc_dtype: on-device partial-histogram dtype, as a string so the
            config stays hashable ("int32" | "int64" | "float32").
        interpret: pallas interpret mode; ``None`` = interpret off-TPU.
        chunk_dyads: streaming chunk size — dyads materialized on device per
            execution step.  ``None`` picks a bounded default.  The plan
            caps the chunk at the graph's dyad-count bucket so small graphs
            don't pad up to a full default chunk.  Every chunk has the same
            padded shape, so one trace serves any graph whose metadata
            buckets match (and graphs whose dyad tiles exceed device memory
            still run).
        device_accum: ``True`` (the default via ``None``) runs the
            device-resident pipeline: dyads are enumerated, bucketed and
            chunk-sliced on device, partial counts accumulate **on device**
            across chunks as an int32 hi/lo pair (no x64 requirement), and
            one device→host transfer completes the run — the paper's
            single end-of-run merge, on every backend (the pallas bucket
            schedule is derived host-side from the degree arrays, so it
            costs no control fetch).  ``False`` restores the synchronous
            baseline: host-side dyad enumeration, per-chunk upload, and a
            blocking per-chunk device→host transfer with host int64
            accumulation (kept runnable for benchmark comparison via
            ``benchmarks/run.py --sync-baseline``).
        pipeline_depth: max in-flight chunks per device in the
            device-resident path (double-buffering depth).  The dispatcher
            enqueues chunk ``k + depth`` while chunk ``k`` still computes,
            then applies backpressure (a non-transferring block) so device
            queue memory stays bounded.  ``1`` degenerates to lockstep
            dispatch; ``2`` (default) is classic double buffering.
        schedule: chunk scheduling policy — ``"static"`` (default) runs
            the in-order single-device loop, bit-identical to the
            pre-executor engine; ``"dynamic"`` carves the dyad stream
            into chunks of roughly equal *predicted* work (the
            :mod:`repro.core.balance` degree cost model — heavy-degree
            dyads get smaller chunks) and dispatches them to the
            executor's device pool with a work-queue policy, the jax
            analogue of the paper's OpenMP dynamic scheduling.  See
            :mod:`repro.engine.executor`.
        n_executor_devices: executor device-pool width for
            ``schedule="dynamic"`` (``None`` = every visible device;
            clamped to the visible count).  Ignored — normalized to 1 —
            under ``schedule="static"`` and on the distributed backend,
            whose mesh already owns every device.  Exercise multi-device
            pools on CPU via
            ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
        delta_threshold: incremental-census cost-model cutoff, in
            ``(0, 1]``.  ``Plan.apply_delta`` runs the affected-subset
            correction only while the mutation footprint (affected dyads
            over the larger dyad stream) stays at or below this
            fraction; above it the full pass is cheaper and runs
            instead.  The default ``0.5`` is the delta pass's break-even
            — it walks the affected set twice, once per graph version.
            ``1.0`` always prefers the delta path.
        max_attempts: bounded retry budget per chunk dispatch (>= 1).  A
            failed chunk is re-dispatched — on the static schedule in
            place, on the dynamic schedule re-queued onto surviving pool
            devices — up to this many total attempts before the run
            raises :class:`~repro.engine.executor.ChunkRetryError`.
            Chunk kernels are functional (a failed attempt never touches
            the accumulator), so recovered runs are bit-identical to
            fault-free runs and still cost one device→host sync.
        backend_fallback: enable the pallas→xla rung of the degradation
            ladder — a pallas compile or runtime failure demotes the
            plan to the xla backend (recorded in ``Plan.degradation``)
            instead of failing the run.  ``False`` re-raises.
        schedule_fallback: enable the dynamic→static rung — a dynamic
            schedule whose device pool is exhausted (every device lost
            or quarantined) re-runs the task list in-order on a single
            device instead of failing the run.  ``False`` re-raises
            :class:`~repro.engine.executor.PoolExhaustedError`.
        reorder: locality-aware vertex relabeling applied before chunk
            dispatch — ``"none"`` (default, no relabeling), ``"degree"``
            (hubs first), ``"bfs"`` (Gorder-style frontier order) or
            ``"rcm"`` (reverse Cuthill–McKee); see
            :mod:`repro.core.reorder`.  The permutation is computed
            host-side once per (plan, graph) and memoized, execution runs
            on the relabeled graph, and raw bins map back through the
            inverse permutation, so results stay bit-identical to
            ``"none"`` for every registered op on every backend and
            schedule — including through ``Plan.apply_delta``, whose
            deltas stay in original vertex ids.  Part of the cache key —
            reordered and plain plans never share compiled state.
        fault_plan: a deterministic
            :class:`~repro.engine.faults.FaultPlan` injected into this
            plan's dispatch paths (``None`` = inherit the
            ``REPRO_FAULT_PLAN`` environment plan if set; an explicitly
            inert ``FaultPlan()`` opts out even under the environment
            hook).  Part of the cache key — faulty and clean plans never
            share compiled state.
        partitions: number of contiguous vertex-range graph shards
            (``None``/``1`` = the unpartitioned single-device CSR).
            With ``partitions > 1`` the engine splits the CSR into
            owned-dyad-balanced vertex ranges, builds each shard a local
            CSR plus a halo of remote neighbor rows, and runs the census
            one shard context at a time — per-device memory is bounded by
            the LARGEST SHARD, not the graph, results stay bit-identical
            to the unpartitioned path for every registered op on every
            backend and schedule, and the run still costs ONE device→host
            sync (shard accumulators merge on the primary device).  See
            :mod:`repro.engine.partition`.  Requires the device-resident
            path (``device_accum`` must not be ``False``) and every op to
            honor the ``delta_local`` locality contract.  Part of the
            cache key.
        spill: out-of-core staging for partitioned runs — ``None``/
            ``False`` (default) stages each shard's dyad list in host
            RAM; ``True`` stages it through memory-mapped scratch files
            in a fresh temp directory (removed after the run); a string
            names the scratch directory to use.  With an mmap-backed
            graph (:func:`repro.core.graph.from_edges_mmap`) peak host
            RAM is one shard's staging buffer, so a dyad stream larger
            than memory completes — ``stats["partition"]`` reports the
            measured ``max_stage_bytes`` against the full
            ``stream_bytes``.  Only meaningful with ``partitions > 1``.
        partition_mode: shard residency policy for ``partitions > 1``
            (``None`` resolves per backend; rejected when
            ``partitions`` is ``None``/``1``).  ``"pool"`` — the
            xla/pallas default — places every shard's local CSR and
            hi/lo accumulator on a distinct executor-pool device
            SIMULTANEOUSLY (resident for the whole run, one counted
            host→device staging per shard), fills halos with a
            device-side exchange (owner shards serve their rows via
            ``jax.device_put`` peer transfers), and drives all shards
            through the executor workqueue at once — aggregate pool
            memory, not the largest single device, bounds graph size,
            and shards overlap in wall time
            (``stats["partition"]["shard_overlap"]``).  ``"serial"``
            runs one shard context at a time pinned to the primary
            device — the out-of-core mode, and the default whenever
            ``spill`` is set; peak device memory is ONE shard.  ``"mesh"`` — the
            distributed-backend default — stacks shard contexts along
            the mesh axis and runs waves of ``shard_map``, one shard
            per mesh device per wave.  ``"mesh"`` requires the
            distributed backend and ``"pool"`` everything but (the
            mesh already owns every device).  All three modes are
            bit-identical to ``partitions=1`` and cost ONE device→host
            sync.  Part of the cache key (normalized at compile).
    """

    backend: str = "auto"
    batch: int = 256
    block: Optional[int] = None
    k: Optional[int] = None
    buckets: Tuple[int, ...] = (32, 128, 512)
    strategy: str = "sorted_snake"
    weight_model: str = "canonical_uniform"
    acc_dtype: str = "int32"
    interpret: Optional[bool] = None
    chunk_dyads: Optional[int] = None
    device_accum: Optional[bool] = None
    pipeline_depth: int = 2
    schedule: str = "static"
    n_executor_devices: Optional[int] = None
    delta_threshold: float = 0.5
    max_attempts: int = 3
    backend_fallback: bool = True
    schedule_fallback: bool = True
    reorder: str = "none"
    fault_plan: Optional[FaultPlan] = None
    partitions: Optional[int] = None
    spill: "Optional[bool | str]" = None
    partition_mode: Optional[str] = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {self.backend!r}")
        if self.acc_dtype not in _ACC_DTYPES:
            raise ValueError(f"acc_dtype must be one of {tuple(_ACC_DTYPES)}")
        if self.batch < 1:
            raise ValueError("batch must be >= 1")
        if self.block is not None and self.block < 1:
            raise ValueError("block must be >= 1")
        # normalize so list-valued buckets still hash (the config is a
        # cache key), then validate the tile-width ladder up front.
        object.__setattr__(self, "buckets",
                           tuple(int(b) for b in self.buckets))
        if not self.buckets:
            raise ValueError("buckets must be non-empty")
        prev = 0
        for b in self.buckets:
            if b < 1:
                raise ValueError(f"buckets must be positive, got {b}")
            if b <= prev:
                raise ValueError("buckets must be strictly increasing, "
                                 f"got {self.buckets}")
            prev = b
        if self.chunk_dyads is not None and self.chunk_dyads < 1:
            raise ValueError(
                f"chunk_dyads must be >= 1 (got {self.chunk_dyads}); use "
                "None for the bounded default")
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1 (got {self.pipeline_depth}); "
                "1 = lockstep dispatch, 2 = double buffering")
        if self.schedule not in SCHEDULES:
            raise ValueError(f"schedule must be one of {SCHEDULES}, "
                             f"got {self.schedule!r}")
        if self.n_executor_devices is not None and self.n_executor_devices < 1:
            raise ValueError(
                f"n_executor_devices must be >= 1 (got "
                f"{self.n_executor_devices}); use None for every visible "
                "device")
        if not (0.0 < float(self.delta_threshold) <= 1.0):
            raise ValueError(
                f"delta_threshold must be in (0, 1] (got "
                f"{self.delta_threshold}); it is the affected-dyad "
                "fraction above which apply_delta falls back to a full "
                "recompute — 1.0 always prefers the delta path")
        object.__setattr__(self, "delta_threshold",
                           float(self.delta_threshold))
        if self.max_attempts < 1:
            raise ValueError(
                f"max_attempts must be >= 1 (got {self.max_attempts}); it "
                "is the total dispatch budget per chunk — 1 disables retry")
        for flag in ("backend_fallback", "schedule_fallback"):
            if not isinstance(getattr(self, flag), bool):
                raise ValueError(
                    f"{flag} must be a bool (got "
                    f"{getattr(self, flag)!r}); it toggles one rung of "
                    "the degradation ladder")
        if self.reorder not in REORDERS:
            raise ValueError(
                f"reorder must be one of {REORDERS}, got {self.reorder!r}; "
                "'none' disables relabeling, 'degree' packs hubs first, "
                "'bfs' uses Gorder-style frontier order, 'rcm' is reverse "
                "Cuthill-McKee")
        if self.fault_plan is not None and not isinstance(self.fault_plan,
                                                          FaultPlan):
            raise ValueError(
                f"fault_plan must be a FaultPlan or None, got "
                f"{type(self.fault_plan).__name__}")
        if self.partitions is not None and (
                not isinstance(self.partitions, int)
                or isinstance(self.partitions, bool)
                or self.partitions < 1):
            raise ValueError(
                f"partitions must be an int >= 1 or None (got "
                f"{self.partitions!r}); it is the number of contiguous "
                "vertex-range graph shards — None/1 is the unpartitioned "
                "single-device CSR")
        if self.spill is not None and not isinstance(self.spill, (bool, str)):
            raise ValueError(
                f"spill must be None, a bool, or a scratch-directory path "
                f"(got {type(self.spill).__name__}); True stages shard "
                "dyad lists through memory-mapped temp files, a string "
                "names the scratch directory")
        if self.partition_mode is not None:
            if self.partition_mode not in PARTITION_MODES:
                raise ValueError(
                    f"partition_mode must be one of {PARTITION_MODES} or "
                    f"None, got {self.partition_mode!r}; 'pool' makes every "
                    "shard resident on a distinct executor-pool device "
                    "simultaneously (device-side halo exchange), 'serial' "
                    "runs one shard context at a time on the primary device "
                    "(the out-of-core mode), 'mesh' runs shard waves via "
                    "shard_map on the distributed backend's mesh")
            if self.partitions is None or self.partitions == 1:
                raise ValueError(
                    f"partition_mode={self.partition_mode!r} requires "
                    "partitions > 1 — an unpartitioned run has no shards "
                    "to place; set partitions or drop partition_mode")
        if (self.partitions is not None and self.partitions > 1
                and self.device_accum is False):
            raise ValueError(
                f"partitions={self.partitions} requires the "
                "device-resident path: the synchronous baseline "
                "(device_accum=False) has no on-device accumulator to "
                "merge shard results into in one sync — drop "
                "device_accum=False or set partitions=1")

    @property
    def acc_jnp_dtype(self):
        return _ACC_DTYPES[self.acc_dtype]

    def resolve_backend(self) -> str:
        """Pin ``"auto"`` to a concrete backend for the current process."""
        if self.backend != "auto":
            return self.backend
        if jax.default_backend() == "tpu":
            return "pallas"
        return "distributed" if len(jax.devices()) > 1 else "xla"

    def resolve_chunk(self) -> int:
        """Streaming chunk size, rounded up to a whole number of batches."""
        c = self.chunk_dyads if self.chunk_dyads is not None else 8192
        return max(self.batch, ((c + self.batch - 1) // self.batch) * self.batch)

    def resolve_device_accum(self) -> bool:
        """Device-resident pipeline on/off; ``None`` means on."""
        return True if self.device_accum is None else self.device_accum

    def resolve_executor_devices(self) -> int:
        """Executor pool width for the current process: 1 under the
        static schedule, else ``n_executor_devices`` (``None`` = all)
        clamped to the visible device count."""
        if self.schedule != "dynamic":
            return 1
        n = (self.n_executor_devices if self.n_executor_devices is not None
             else len(jax.devices()))
        return max(1, min(n, len(jax.devices())))

    def resolve_partitions(self) -> int:
        """Graph shard count; ``None`` means unpartitioned (1)."""
        return 1 if self.partitions is None else int(self.partitions)

    def resolve_partition_mode(self, backend: "Optional[str]" = None) -> "Optional[str]":
        """Shard residency mode for the resolved backend: ``None`` for
        unpartitioned plans, the explicit mode when set, ``"serial"``
        when ``spill`` is active (out-of-core staging promises ONE
        resident shard — concurrent residency would break the bounded
        staging peak), else ``"mesh"`` on the distributed backend (whose
        mesh owns every device) and ``"pool"`` everywhere else.
        ``compile()`` normalizes the config through this, so ``None``
        and the mode it resolves to share one plan-cache entry."""
        if self.resolve_partitions() == 1:
            return None
        if self.partition_mode is not None:
            return self.partition_mode
        if self.resolve_spill():
            return "serial"
        backend = backend if backend is not None else self.resolve_backend()
        return "mesh" if backend == "distributed" else "pool"

    def resolve_spill(self) -> "Optional[bool | str]":
        """Spill policy with the inert ``False`` normalized to ``None``
        (so off-by-default and explicitly-off configs share one plan)."""
        return None if self.spill is False else self.spill

    def resolve_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return jax.default_backend() != "tpu"

    def resolve_block(self) -> int:
        return self.block if self.block is not None else min(self.batch, 32)


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed place.

    Call once at the start of a program, before its first compile (never
    from module import).  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    already reads it and nothing is changed here; otherwise the cache is
    ``<checkout>/.jax_cache`` — a fixed path, since the path is part of
    what makes a cache entry found again.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


#: Census-era name for :class:`EngineConfig` — the same class (not a
#: subclass), so wrapper-API and new-API configs compare and hash equal.
CensusConfig = EngineConfig
