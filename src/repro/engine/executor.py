"""Degree-aware adaptive chunk scheduling across a device pool.

The paper's multicore speedups (56x max) come from OpenMP **dynamic
scheduling** of degree-skewed dyad work across hardware threads, and its
GPU results hinge on degree-based load balancing; Dehne & Yogaratnam
(PAPERS.md) identify per-thread work imbalance as the dominant cost for
irregular graphs.  This module is the engine's analogue: an
:class:`Executor` owns a pool of devices and dispatches
:class:`ChunkTask` descriptors — contiguous spans of the device-resident
dyad stream, carved by a *cost model* rather than a fixed ``chunk_size``
(see :func:`repro.core.balance.chunk_bounds_by_cost`) — with a
work-queue policy:

  * ``schedule="static"`` (default): the single-device in-order loop the
    engine always ran — bit-identical to the pre-executor engine, with
    the same double-buffering backpressure (:func:`_throttle`).
  * ``schedule="dynamic"``: one worker thread per pool device pulls the
    next task from a shared queue as soon as its previous dispatch
    clears the pipeline window — the jax analogue of OpenMP
    ``schedule(dynamic)``.  A device stuck on a heavy-degree chunk
    simply pulls fewer chunks; no task assignment is precomputed.

Per-device compiled replicas come for free: the plan's chunk unit is one
``jax.jit`` callable, and jit specializes (and caches) one executable
per committed input device, so the first task a device pulls compiles
its replica and every later task reuses it.

Each worker folds its chunks into a device-local int32 hi/lo
accumulator; the pool merges worker accumulators on the primary device
(:func:`_merge_accs` — exact integer addition, so the merged totals are
bit-identical to the static path for any task-to-device assignment) and
ONE device→host transfer (:func:`_acc_fetch`) completes the run
regardless of pool size.

**Fault tolerance** (hours-long runs on a pool must survive a failed
kernel launch or a lost device): every chunk dispatch has a bounded
retry budget (``EngineConfig.max_attempts``).  Chunk kernels are
functional — a failed attempt never touches the accumulator — so a
retried chunk folds exactly once and recovered runs stay bit-identical
to fault-free runs, still in one device→host sync.  On the dynamic
schedule a failed task is **re-queued onto surviving devices**; a device
that raises :class:`~repro.engine.faults.DeviceLostError` (or fails
:data:`Executor.QUARANTINE_AFTER` dispatches) is **quarantined** out of
the pool for the rest of the run — its already-folded accumulator stays
valid (only successful folds touched it) and merges normally.  A pool
with every device gone raises :class:`PoolExhaustedError`, which
:meth:`Executor.run` converts into the degradation ladder's
dynamic→static rung (``EngineConfig.schedule_fallback``): the full task
list re-runs in-order on the primary device with device-loss injection
suppressed (fresh-device semantics).  All recovery actions land in
``stats["faults"]`` counters and a bounded ``stats["fault_events"]``
trace — deterministic under a seeded
:class:`~repro.engine.faults.FaultPlan`, which is also how every one of
these paths is exercised in CI (see :mod:`repro.engine.faults`).

Exercise the pool on CPU CI with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.spans import span
from .faults import DeviceLostError, InjectedFault, resolve_faults

# the device accumulator is an int32 (hi, lo) pair: count = hi * 2**30 + lo
# with 0 <= lo < 2**30 — exact for totals up to 2**61 without enabling x64.
# Per-fold deltas must stay below 2**30, which holds whenever
# batch * n < 2**30 (the same order of invariant the int32 scan partials
# already required; GraphOp kernels promise the same bound).
_ACC_SHIFT = 30

#: cap on the per-plan fault-event trace (it is a diagnostic, not a log).
_MAX_EVENTS = 512


def _acc_update(hi, lo, delta):
    """Fold a non-negative int32 partial into the hi/lo accumulator."""
    lo = lo + delta.astype(jnp.int32)
    carry = lo >> _ACC_SHIFT
    return hi + carry, lo - (carry << _ACC_SHIFT)


def _acc_fetch(plan, hi, lo) -> np.ndarray:
    """THE device→host transfer of a device-resident run (counted)."""
    plan.stats["host_syncs"] += 1
    with span("fetch"):
        packed = np.asarray(jnp.stack([hi, lo]), dtype=np.int64)
    return (packed[0] << _ACC_SHIFT) + packed[1]


@jax.jit
def _merge_accs(hi_t, lo_t, hi_d, lo_d):
    """Fold one worker's hi/lo pair into the pool total (on the primary
    device).  ``lo_d < 2**30`` by the accumulator invariant, so it is a
    valid delta; the hi words add directly.  Pure integer arithmetic —
    the merged total is exact for any partition of the task stream."""
    hi_t, lo_t = _acc_update(hi_t, lo_t, lo_d)
    return hi_t + hi_d, lo_t


def _throttle(window: collections.deque, ref, depth: int) -> None:
    """Double-buffering backpressure: allow ``depth`` chunks in flight.

    Blocks on the dispatch ``depth`` chunks back (a wait, not a transfer)
    so the device work queue stays bounded while chunk ``k + depth`` is
    being enqueued as chunk ``k`` computes.
    """
    window.append(ref)
    if len(window) > max(1, depth):
        with span("wait"):
            window.popleft().block_until_ready()


class WorkerFailures(RuntimeError):
    """Aggregate of *secondary* concurrent worker failures, attached as
    the ``__cause__`` of the primary raised error so a multi-device
    failure is fully diagnosable from one traceback (the pre-fix
    executor raised ``errors[0]`` and silently dropped the rest).  The
    individual exceptions are in ``.errors``."""

    def __init__(self, errors):
        self.errors = list(errors)
        super().__init__(
            f"{len(self.errors)} additional concurrent worker failure(s): "
            + "; ".join(repr(e) for e in self.errors))


class ChunkRetryError(RuntimeError):
    """A chunk kept failing after its full ``max_attempts`` dispatch
    budget (possibly across several pool devices).  The last underlying
    failure is the ``__cause__``; every attempt's exception is in
    ``.attempts``."""

    def __init__(self, message, attempts=()):
        self.attempts = list(attempts)
        super().__init__(message)


class PoolExhaustedError(RuntimeError):
    """Every device in a dynamic-schedule pool was lost or quarantined
    while tasks remained queued.  With
    ``EngineConfig.schedule_fallback=True`` (the default) the executor
    converts this into the ladder's static single-device re-run instead
    of surfacing it."""


def _raise_worker_errors(errors):
    """Raise the primary worker error with any concurrent secondaries
    attached via ``__cause__`` (:class:`WorkerFailures`) — nothing is
    silently dropped."""
    primary, rest = errors[0], errors[1:]
    if rest:
        raise primary from WorkerFailures(rest)
    raise primary


class ChunkTask(NamedTuple):
    """One schedulable span of the dyad stream: dyads ``[start, end)``,
    its cost-model-predicted work (drives the executor's balance stats),
    and an optional static-argument key (the pallas backend stores the
    bucket tile width ``K`` here so each task dispatches the right
    kernel specialization)."""

    start: int
    end: int
    cost: float = 0.0
    key: Optional[int] = None


class Executor:
    """A device pool + dispatch policy for one plan's chunk tasks.

    Built by :class:`repro.engine.plan.Plan` from its
    :class:`~repro.engine.EngineConfig` (``schedule``,
    ``n_executor_devices``, ``max_attempts``, ``schedule_fallback``,
    ``fault_plan``); the distributed backend pins the pool to a single
    slot because its mesh already owns every device (shard_map is the
    parallelism there — the executor contributes only the chunk loop).
    See the module docstring for the scheduling and fault-recovery
    policies.

    :meth:`run` drives ``step(ctx, hi, lo, task) -> (hi, lo)`` over the
    task list, where ``ctx = place(device)`` is the backend's
    device-resident context (graph arrays + dyad stream; ``place(None)``
    must return the default-placement context unchanged — that keeps the
    static path free of extra transfers).  Dispatch counts land in
    ``stats["device_chunks"]`` (``{device index: chunks}``) — the
    occupancy signal :meth:`repro.serve.CensusService.stats` aggregates.
    """

    #: generic (non-device-loss) dispatch failures on one device before
    #: it is quarantined — provided at least one other device survives.
    QUARANTINE_AFTER = 2

    def __init__(self, config, stats: dict, *, n_devices: int = 1,
                 backend: str = "xla"):
        self.schedule = config.schedule
        self.depth = max(1, config.pipeline_depth)
        self.max_attempts = max(1, config.max_attempts)
        self.schedule_fallback = config.schedule_fallback
        self.backend = backend
        self.faults = resolve_faults(config.fault_plan)
        n = max(1, min(n_devices, len(jax.devices())))
        # a 1-slot pool keeps default placement (device=None): no
        # device_put, no behavior change vs the pre-executor engine.
        self.devices = list(jax.devices()[:n]) if n > 1 else [None]
        self.stats = stats
        self._flock = threading.Lock()
        self._suppress_device_loss = False

    @property
    def n_devices(self) -> int:
        """Pool width (1 = default-device in-order dispatch)."""
        return len(self.devices)

    def _bump(self, dev_index: int, count: int) -> None:
        dc = self.stats.setdefault("device_chunks", {})
        dc[dev_index] = dc.get(dev_index, 0) + count

    # -- fault bookkeeping (thread-safe; counters + bounded trace) -----------

    def _fault_stats(self) -> dict:
        return self.stats.setdefault(
            "faults", dict(chunk_failures=0, retries=0, device_losses=0,
                           quarantines=0, backend_fallbacks=0,
                           schedule_fallbacks=0))

    def _note(self, *event, **counters) -> None:
        """Record fault counters and one trace event under the lock."""
        with self._flock:
            fs = self._fault_stats()
            for k, v in counters.items():
                fs[k] = fs.get(k, 0) + v
            if event:
                trace = self.stats.setdefault("fault_events", [])
                if len(trace) < _MAX_EVENTS:
                    trace.append(event)

    # -- fault-aware single dispatch -----------------------------------------

    def _dispatch(self, ctx, hi, lo, task, step, dev_index, ordinal, attempt):
        """One dispatch attempt of ``task`` on pool device ``dev_index``,
        with injection checks from the resolved fault plan (skipped
        entirely — zero overhead — when no plan is active)."""
        f = self.faults
        if f is not None:
            if (not self._suppress_device_loss
                    and f.device_lost(dev_index, ordinal)):
                self._note("device_loss", dev_index, device_losses=1)
                raise DeviceLostError(
                    f"injected loss of pool device {dev_index} at dispatch "
                    f"ordinal {ordinal}")
            if f.runtime_fails(self.backend):
                self._note("runtime_failure", self.backend, task.start,
                           chunk_failures=1)
                raise InjectedFault(
                    f"injected {self.backend} runtime failure for chunk at "
                    f"dyad {task.start}")
            if f.chunk_fails(task.start, attempt):
                self._note("chunk_failure", task.start, attempt,
                           chunk_failures=1)
                raise InjectedFault(
                    f"injected failure for chunk at dyad {task.start} "
                    f"(attempt {attempt})")
            f.maybe_delay(task.start)
        args = {} if task.key is None else {"K": task.key}
        with span("chunk", start=task.start, end=task.end, **args):
            return step(ctx, hi, lo, task)

    def _attempt(self, ctx, hi, lo, task, step, dev_index, ordinal):
        """Bounded-retry dispatch of one task on one device (the static
        path's recovery policy).  Chunk kernels are functional, so a
        failed attempt leaves (hi, lo) untouched and the eventual
        successful fold is bit-identical to a fault-free run."""
        failures: list = []
        for attempt in range(1, self.max_attempts + 1):
            try:
                return self._dispatch(ctx, hi, lo, task, step, dev_index,
                                      ordinal, attempt)
            except Exception as e:  # noqa: BLE001 — KeyboardInterrupt etc.
                # (BaseException) must still abort the run immediately.
                failures.append(e)
                if isinstance(e, DeviceLostError):
                    break  # the device is gone; retrying in place is futile
                if attempt < self.max_attempts:
                    self._note("retry", task.start, attempt, retries=1)
        err = ChunkRetryError(
            f"chunk [{task.start}, {task.end}) failed after "
            f"{len(failures)} attempt(s) on device {dev_index}",
            attempts=failures)
        raise err from failures[-1]

    def run(self, tasks, *, place, step, init):
        """Execute every task; returns the merged (hi, lo) accumulator.

        ``init`` is the run's starting accumulator (it already carries
        the per-run ``once`` contribution) on default placement; the
        result is safe to pass to :func:`_acc_fetch`.  Recovers from
        per-chunk failures (bounded retry / re-queue), quarantines
        failing pool devices, and — when the pool is exhausted under
        ``schedule_fallback=True`` — re-runs the whole task list on the
        ladder's static single-device rung.
        """
        tasks = list(tasks)
        if len(self.devices) == 1:
            try:
                return self._run_inorder(tasks, place, step, init)
            except ChunkRetryError as e:
                # a 1-wide dynamic pool whose only device died is an
                # exhausted pool: same ladder rung as the N-wide case.
                if (self.schedule == "dynamic" and self.schedule_fallback
                        and isinstance(e.__cause__, DeviceLostError)):
                    return self._run_fallback(tasks, place, step, init)
                raise
        try:
            return self._run_workqueue(tasks, place, step, init)
        except PoolExhaustedError:
            if not self.schedule_fallback:
                raise
            return self._run_fallback(tasks, place, step, init)

    def _run_fallback(self, tasks, place, step, init):
        """The dynamic→static degradation rung: re-run the full task
        list in-order on the primary device, with device-loss injection
        suppressed (the rung models re-attaching a fresh device).  The
        accumulator restarts from ``init`` — partial dynamic progress is
        discarded, keeping the result bit-identical to a clean run."""
        self._note("schedule_fallback", "dynamic->static",
                   schedule_fallbacks=1)
        self._suppress_device_loss = True
        try:
            return self._run_inorder(tasks, place, step, init)
        finally:
            self._suppress_device_loss = False

    # -- static: the pre-executor single-device loop + bounded retry ---------

    def _run_inorder(self, tasks, place, step, init):
        ctx = place(self.devices[0])
        hi, lo = init
        window: collections.deque = collections.deque()
        for ordinal, t in enumerate(tasks):
            hi, lo = self._attempt(ctx, hi, lo, t, step, 0, ordinal)
            # chunk + occupancy counters move together so the
            # sum(device_chunks) == chunks invariant holds even if a
            # later task exhausts its retries mid-run.
            self.stats["chunks"] += 1
            self._bump(0, 1)
            _throttle(window, hi, self.depth)
        return hi, lo

    # -- dynamic: worker thread per device, shared task queue ----------------

    def _run_workqueue(self, tasks, place, step, init):
        # queue entries are (task, attempt): a failed task re-queues with
        # attempt + 1 and any surviving worker may pick it up; a task
        # dropped by a *lost* device re-queues at the same attempt (the
        # device was at fault, not the chunk).
        queue: collections.deque = collections.deque((t, 1) for t in tasks)
        qlock = threading.Lock()
        accs: list = [None] * len(self.devices)
        counts = [0] * len(self.devices)
        fatal: list = []
        alive = set(range(len(self.devices)))
        failures = [0] * len(self.devices)

        def quarantine(i: int, reason: str) -> None:
            # callers hold qlock
            alive.discard(i)
            self._note("quarantine", i, reason, quarantines=1)
            if not alive and queue and not fatal:
                fatal.append(PoolExhaustedError(
                    f"all {len(self.devices)} pool devices lost or "
                    f"quarantined with {len(queue)} task(s) remaining"))

        def on_failure(i: int, t, attempt: int, e: Exception) -> None:
            # callers hold qlock
            if isinstance(e, DeviceLostError):
                queue.append((t, attempt))  # chunk not at fault
                quarantine(i, "device_loss")
                return
            failures[i] += 1
            if attempt >= self.max_attempts:
                err = ChunkRetryError(
                    f"chunk [{t.start}, {t.end}) failed after {attempt} "
                    f"attempt(s) across the device pool")
                err.__cause__ = e
                fatal.append(err)
                return
            self._note("retry", t.start, attempt, retries=1)
            queue.append((t, attempt + 1))
            if failures[i] >= self.QUARANTINE_AFTER and len(alive) > 1:
                quarantine(i, "repeated_failures")

        def worker(i: int, dev) -> None:
            # XLA execution releases the GIL, so worker threads overlap
            # on distinct devices; jit compiles this device's replica on
            # its first task and caches it for the rest of the run.
            acc = None
            try:
                try:
                    ctx = place(dev)
                    acc = jax.device_put((jnp.zeros_like(init[0]),
                                          jnp.zeros_like(init[1])), dev)
                except Exception:  # a device whose context cannot even be
                    # placed is dead on arrival: quarantine, don't abort.
                    with qlock:
                        quarantine(i, "placement_failure")
                    return
                window: collections.deque = collections.deque()
                ordinal = 0
                while True:
                    with qlock:
                        if not queue or fatal or i not in alive:
                            break
                        t, attempt = queue.popleft()
                    try:
                        hi, lo = self._dispatch(ctx, *acc, t, step, i,
                                                ordinal, attempt)
                    except Exception as e:
                        ordinal += 1
                        with qlock:
                            on_failure(i, t, attempt, e)
                        continue
                    ordinal += 1
                    acc = (hi, lo)
                    counts[i] += 1
                    _throttle(window, hi, self.depth)
            except BaseException as e:  # noqa: BLE001 — ANY escape must
                # surface in the caller's thread: a silently dead worker
                # would otherwise drop every chunk it had folded and the
                # merged run would under-count with no error raised.
                with qlock:
                    fatal.append(e)
            finally:
                accs[i] = acc

        threads = [threading.Thread(target=worker, args=(i, d), daemon=True)
                   for i, d in enumerate(self.devices)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if fatal:
            # PoolExhaustedError outranks secondary errors: run() turns it
            # into the static-fallback rung, which re-runs everything.
            pool_dead = [e for e in fatal
                         if isinstance(e, PoolExhaustedError)]
            if pool_dead:
                raise pool_dead[0]
            _raise_worker_errors(fatal)
        self.stats["chunks"] += len(tasks)
        for i, c in enumerate(counts):
            if c:
                self._bump(i, c)
        # merge worker accumulators on the primary device: exact integer
        # folds, so the result is independent of the task assignment.  A
        # quarantined worker's accumulator is still valid — only its
        # *successful* folds touched it — and merges like any other.
        hi, lo = init
        primary = self.devices[0]
        for acc in accs:
            if acc is None:
                continue
            hi_d, lo_d = jax.device_put(acc, primary)
            hi, lo = _merge_accs(hi, lo, hi_d, lo_d)
        return hi, lo

    # -- pinned: in-order dispatch of a pre-placed shard context -------------

    def _run_pinned_once(self, tasks, ctx, step, init):
        hi, lo = init
        window: collections.deque = collections.deque()
        for ordinal, t in enumerate(tasks):
            hi, lo = self._attempt(ctx, hi, lo, t, step, 0, ordinal)
            self.stats["chunks"] += 1
            self._bump(0, 1)
            _throttle(window, hi, self.depth)
        return hi, lo

    def run_pinned(self, tasks, *, ctx, step, init, rebuild=None):
        """In-order dispatch on the PRIMARY device with a pre-placed
        context — the partitioned engine's ``partition_mode="serial"``
        rung: the caller stages ``ctx`` exactly once per shard (the
        hoisted ``device_put`` — no per-worker re-staging) and one shard
        context is resident at a time.  Bounded per-chunk retry as on the
        static path; a lost primary device under ``schedule_fallback``
        re-runs this shard's tasks with device-loss injection suppressed
        (fresh-device semantics), re-staging the context via ``rebuild()``
        when provided.  The accumulator restarts from ``init`` on that
        rung — failed attempts never touched it, so recovered results
        stay bit-identical."""
        try:
            return self._run_pinned_once(tasks, ctx, step, init)
        except ChunkRetryError as e:
            if not (self.schedule_fallback
                    and isinstance(e.__cause__, DeviceLostError)):
                raise
            self._note("schedule_fallback", "pinned-rerun",
                       schedule_fallbacks=1)
            self._suppress_device_loss = True
            try:
                return self._run_pinned_once(
                    tasks, ctx if rebuild is None else rebuild(), step, init)
            finally:
                self._suppress_device_loss = False

    # -- sharded: concurrent multi-shard workqueue over the pool -------------

    def run_sharded(self, shard_tasks, *, place, step, init, pstats):
        """Concurrent shard residency: drive EVERY shard's tasks through
        the pool at once (``partition_mode="pool"``).

        ``shard_tasks`` is ``[(shard_id, [ChunkTask, ...]), ...]``; each
        shard is HOMED on one pool device (round-robin) and
        ``place(shard_id, device)`` returns its device-resident context —
        called exactly once per shard per run (the caller counts these as
        ``stats["partition"]["h2d_puts"]``), so shard arrays stay
        resident for the whole run instead of re-staging per worker or
        per chunk.  Each (device, shard) pair accumulates into its own
        hi/lo lane; lanes merge on the primary device via
        :func:`_merge_accs` — exact integer folds, so the merged totals
        are bit-identical to the serial path for ANY homing, interleave,
        or re-home history.  Fault policy extends the workqueue's: a
        failed chunk retries on its home, a lost/quarantined device
        **re-homes its shards onto survivors** (their queued tasks move,
        ``place`` re-stages the context on the new home, the dead
        device's already-folded lanes stay valid and merge normally, and
        ``pstats["rehomes"]`` counts the moves), and an exhausted pool
        under ``schedule_fallback`` re-runs everything in-order on the
        primary device from ``init``.  Per-shard wall-clock intervals
        land in ``pstats["shard_times"]`` — the raw material for the
        ``shard_overlap`` concurrency observable."""
        shard_tasks = [(s, list(ts)) for s, ts in shard_tasks]
        try:
            return self._run_sharded_queue(shard_tasks, place, step, init,
                                           pstats)
        except PoolExhaustedError:
            if not self.schedule_fallback:
                raise
            self._note("schedule_fallback", "dynamic->static",
                       schedule_fallbacks=1)
            self._suppress_device_loss = True
            try:
                hi, lo = init
                for s, ts in shard_tasks:
                    ctx = place(s, self.devices[0])
                    hi, lo = self._run_pinned_once(ts, ctx, step, (hi, lo))
                return hi, lo
            finally:
                self._suppress_device_loss = False

    def _run_sharded_queue(self, shard_tasks, place, step, init, pstats):
        t_base = time.perf_counter()
        times = pstats.setdefault("shard_times", {})
        if len(self.devices) == 1:
            # degenerate pool (static schedule or one visible device):
            # shards run in-order on the primary device — still exactly
            # one staging per shard, still exact accumulator chaining.
            hi, lo = init
            for s, ts in shard_tasks:
                ctx = place(s, self.devices[0])
                start = time.perf_counter() - t_base
                hi, lo = self.run_pinned(
                    ts, ctx=ctx, step=step, init=(hi, lo),
                    rebuild=lambda s=s: place(s, self.devices[0]))
                times[s] = dict(start=start,
                                end=time.perf_counter() - t_base,
                                tasks=len(ts), device=0)
            return hi, lo
        n = len(self.devices)
        home: dict = {}
        queues = [collections.deque() for _ in range(n)]
        by_dev: list = [[] for _ in range(n)]
        for k, (s, ts) in enumerate(shard_tasks):
            home[s] = k % n
            by_dev[k % n].append((s, ts))
        for i, lst in enumerate(by_dev):
            # interleave this device's shards round-robin so same-device
            # shards advance together (P > pool width still overlaps).
            iters = [iter(ts) for _, ts in lst]
            names = [s for s, _ in lst]
            while iters:
                keep_i, keep_n = [], []
                for s, it in zip(names, iters):
                    t = next(it, None)
                    if t is not None:
                        queues[i].append((s, t, 1))
                        keep_i.append(it)
                        keep_n.append(s)
                iters, names = keep_i, keep_n
        cond = threading.Condition()
        lanes: dict = {}   # (dev_index, shard) -> device (hi, lo) lane
        ctxs: dict = {}    # shard -> context on its CURRENT home device
        counts = [0] * n
        fatal: list = []
        alive = set(range(n))
        failures = [0] * n
        first: dict = {}
        last: dict = {}
        task_total = sum(len(ts) for _, ts in shard_tasks)
        # tasks not yet folded or failed: workers with an empty queue WAIT
        # on this (a re-home may hand them work later) instead of exiting
        # — an early exit would strand re-homed tasks and undercount.
        pending = [task_total]

        def rehome(i: int) -> None:
            # callers hold cond: device i is out — move its remaining
            # queue onto survivors and re-point its shards' homes; the
            # new home's worker re-places each context on first touch.
            moved = queues[i]
            queues[i] = collections.deque()
            if not alive:
                if moved and not fatal:
                    fatal.append(PoolExhaustedError(
                        f"all {n} pool devices lost or quarantined with "
                        f"{len(moved)} task(s) remaining"))
                cond.notify_all()
                return
            survivors = sorted(alive)
            assigned: dict = {}
            for s, t, a in moved:
                j = assigned.get(s)
                if j is None:
                    j = survivors[len(assigned) % len(survivors)]
                    assigned[s] = j
                    home[s] = j
                    ctxs.pop(s, None)
                    pstats["rehomes"] = pstats.get("rehomes", 0) + 1
                    self._note("shard_rehome", s, i, j)
                queues[j].append((s, t, a))
            cond.notify_all()

        def quarantine(i: int, reason: str) -> None:
            # callers hold cond
            alive.discard(i)
            self._note("quarantine", i, reason, quarantines=1)
            rehome(i)

        def on_failure(i: int, s, t, attempt: int, e: Exception) -> None:
            # callers hold cond
            if isinstance(e, DeviceLostError):
                queues[i].appendleft((s, t, attempt))  # chunk not at fault
                quarantine(i, "device_loss")
                return
            failures[i] += 1
            if attempt >= self.max_attempts:
                err = ChunkRetryError(
                    f"chunk [{t.start}, {t.end}) of shard {s} failed "
                    f"after {attempt} attempt(s)")
                err.__cause__ = e
                fatal.append(err)
                cond.notify_all()
                return
            self._note("retry", t.start, attempt, retries=1)
            queues[i].append((s, t, attempt + 1))
            if failures[i] >= self.QUARANTINE_AFTER and len(alive) > 1:
                quarantine(i, "repeated_failures")
            cond.notify_all()

        def worker(i: int, dev) -> None:
            window: collections.deque = collections.deque()
            ordinal = 0
            mine: set = set()
            try:
                while True:
                    with cond:
                        # an empty queue is not the end: wait while other
                        # devices still hold pending tasks — a loss there
                        # re-homes work onto this queue.
                        while (not fatal and i in alive and not queues[i]
                               and pending[0] > 0):
                            cond.wait(0.05)
                        if fatal or i not in alive or not queues[i]:
                            break
                        s, t, attempt = queues[i].popleft()
                        ctx = ctxs.get(s)
                        first.setdefault(s, time.perf_counter() - t_base)
                    if ctx is None:
                        try:
                            ctx = place(s, dev)
                        except Exception:
                            with cond:
                                queues[i].appendleft((s, t, attempt))
                                quarantine(i, "placement_failure")
                            break
                        with cond:
                            ctxs[s] = ctx
                    with cond:
                        lane = lanes.get((i, s))
                    if lane is None:
                        lane = jax.device_put((jnp.zeros_like(init[0]),
                                               jnp.zeros_like(init[1])), dev)
                    try:
                        hi, lo = self._dispatch(ctx, *lane, t, step, i,
                                                ordinal, attempt)
                    except Exception as e:
                        ordinal += 1
                        with cond:
                            on_failure(i, s, t, attempt, e)
                        continue
                    ordinal += 1
                    mine.add(s)
                    counts[i] += 1
                    with cond:
                        lanes[(i, s)] = (hi, lo)
                        pending[0] -= 1
                        if pending[0] <= 0:
                            cond.notify_all()
                    _throttle(window, hi, self.depth)
            except BaseException as e:  # noqa: BLE001 — see _run_workqueue
                with cond:
                    fatal.append(e)
                    cond.notify_all()
            finally:
                # block on this worker's lanes so the recorded end times
                # reflect COMPLETED device work, not just dispatch.
                for s in mine:
                    with cond:
                        lane = lanes.get((i, s))
                    if lane is not None:
                        try:
                            lane[0].block_until_ready()
                        except Exception:  # timing only — never fatal
                            pass
                    with cond:
                        last[s] = max(last.get(s, 0.0),
                                      time.perf_counter() - t_base)

        threads = [threading.Thread(target=worker, args=(i, d), daemon=True)
                   for i, d in enumerate(self.devices)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if fatal:
            pool_dead = [e for e in fatal
                         if isinstance(e, PoolExhaustedError)]
            if pool_dead:
                raise pool_dead[0]
            _raise_worker_errors(fatal)
        self.stats["chunks"] += task_total
        for i, c in enumerate(counts):
            if c:
                self._bump(i, c)
        for s, ts in shard_tasks:
            if s in first:
                times[s] = dict(start=first[s],
                                end=max(last.get(s, first[s]), first[s]),
                                tasks=len(ts), device=home[s])
        # merge every (device, shard) lane on the primary device: exact
        # integer folds — bit-identical for any homing or re-home history
        # (a quarantined device's lanes hold only successful folds).
        hi, lo = init
        primary = self.devices[0]
        for key in sorted(lanes):
            hi_d, lo_d = jax.device_put(lanes[key], primary)
            hi, lo = _merge_accs(hi, lo, hi_d, lo_d)
        return hi, lo
