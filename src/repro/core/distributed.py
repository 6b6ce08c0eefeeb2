"""Multi-pod distributed Triad Census via ``shard_map`` (see repro.compat).

.. deprecated:: prefer ``repro.engine.compile_census`` with
   ``CensusConfig(backend="distributed")`` — it adds the plan cache and
   chunked streaming on top of the same shard_map schedule built here.

Maps the paper's parallelization (one task queue per hardware thread,
decoupled per-thread census arrays, single final merge) onto an SPMD mesh:

  * every mesh device receives one **static task shard** from
    :mod:`repro.core.balance` (the task-queue analogue),
  * the graph CSR is replicated (the paper's shared-memory model),
  * each device accumulates a private 16-bin census (the decoupled local
    census array) and a single ``psum`` over all mesh axes performs the
    paper's end-of-run merge — the only communication in the whole job.

The collective schedule is therefore exactly one 64-byte all-reduce, which
is why the census is compute-bound at any pod size (see EXPERIMENTS.md).
"""
from __future__ import annotations

import math
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .census import make_census_batch_fn
from .graph import CSRGraph


def make_census_fn_for_mesh(mesh: jax.sharding.Mesh, *, K: int | None = None,
                            member_iters: int | None = None, batch: int = 256,
                            acc_dtype=jnp.int32, on_trace=None,
                            batch_fn=None, n_bins: int = 16):
    """Build a shard_map'd per-batch kernel sweep over every device of
    ``mesh``.

    The single definition of the SPMD schedule — the legacy
    ``make_distributed_census_fn`` and the engine's distributed backend
    both call this.  The returned jitted fn takes ``(graph_arrays, n,
    tasks_u, tasks_v, valid)`` with task arrays shaped ``(n_devices, L)``
    (L a multiple of ``batch``) and returns the merged ``(n_bins,)``
    partial counts.  By default the kernel is the triad census built from
    ``K`` / ``member_iters``; the engine's fused multi-analytic path
    passes its own ``batch_fn`` (any ``(arrays, n, u, v, valid) ->
    (n_bins,)`` additive kernel — see :mod:`repro.engine.ops`) plus the
    matching ``n_bins``.  ``on_trace`` (if set) is invoked as a
    trace-time side effect — the engine uses it to count retraces.
    """
    if batch_fn is None:
        batch_fn = make_census_batch_fn(K, member_iters, acc_dtype)
    axes = tuple(mesh.axis_names)

    def device_census(arrays, n, u, v, valid):
        if on_trace is not None:
            on_trace()
        # u, v, valid: (1, L) local block — one task shard per device.
        u, v, valid = u[0], v[0], valid[0]
        steps = u.shape[0] // batch

        def step(carry, xs):
            uu, vv, va = xs
            return carry + batch_fn(arrays, n, uu, vv, va), None

        init = jax.lax.pcast(jnp.zeros((n_bins,), acc_dtype), axes,
                             to="varying")
        counts, _ = jax.lax.scan(
            step, init,
            (u.reshape(steps, batch), v.reshape(steps, batch),
             valid.reshape(steps, batch)),
        )
        # the paper's final merge: one tree-reduction over all workers.
        for ax in axes:
            counts = jax.lax.psum(counts, ax)
        return counts

    shmap = jax.shard_map(
        device_census,
        mesh=mesh,
        in_specs=(P(), P(), P(axes), P(axes), P(axes)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(shmap)


def make_distributed_census_fn(g: CSRGraph, mesh: jax.sharding.Mesh, *,
                               batch: int = 256, K: int | None = None,
                               acc_dtype=jnp.int32):
    """Legacy builder: derives K/member_iters from ``g`` (see
    :func:`make_census_fn_for_mesh` for the schedule itself)."""
    K = K or max(1, g.max_deg)
    member_iters = max(1, math.ceil(math.log2(max(g.max_deg, g.max_out_deg, 1) + 1))) + 1
    return make_census_fn_for_mesh(mesh, K=K, member_iters=member_iters,
                                   batch=batch, acc_dtype=acc_dtype)


def distributed_triad_census(
    g: CSRGraph,
    mesh: jax.sharding.Mesh,
    *,
    weight_model: str = "canonical_uniform",
    strategy: str = "sorted_snake",
    batch: int = 256,
    K: int | None = None,
):
    """Partition, balance, and run the census over all devices of ``mesh``.

    .. deprecated:: thin shim over ``repro.engine`` (plan cache + chunked
       streaming included).  Returns ``(CensusResult, task_stats)`` where
       ``task_stats`` is the lightweight per-shard load summary (it has
       ``.imbalance`` / ``.weights`` like the old ``ShardedTasks`` but not
       the task arrays; call :func:`repro.core.balance.pack_tasks` if you
       need those).
    """
    from ..engine import CensusConfig, compile_census

    warnings.warn(
        "repro.core.distributed.distributed_triad_census is deprecated; use "
        "repro.engine.compile_census with CensusConfig(backend='distributed')",
        DeprecationWarning, stacklevel=2)
    cfg = CensusConfig(backend="distributed", batch=batch, k=K,
                       strategy=strategy, weight_model=weight_model)
    plan = compile_census(g, cfg, mesh=mesh)
    res = plan.run(g)
    return res, plan.last_task_stats
