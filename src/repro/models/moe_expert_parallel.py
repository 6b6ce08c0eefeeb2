"""True expert-parallel MoE via ``shard_map`` + explicit ``all_to_all``.

The §Perf log (EXPERIMENTS.md, cell 2) ends with grouped dispatch still
collective-bound because GSPMD realizes the buffer reshard from
batch-layout to expert-layout as all-gather + all-reduce.  This module is
the documented next iteration, written manually inside ``shard_map``:

  1. each model shard takes its 1/ep slice of the local tokens (so routing,
     sort and scatter are non-redundant across the TP axis),
  2. one ``all_to_all`` moves capacity slots from token-layout to
     expert-layout,
  3. local expert FFNs (experts are sharded over 'model'),
  4. the inverse ``all_to_all`` + an ``all_gather`` of the combined output
     restore the replicated activation layout.

Cross-device traffic = 2 x a2a(buffer/ep) + 1 x all_gather(y) — no
all-reduce, no replicated capacity buffer.  Kept separate from
``moe_apply`` (the jit/GSPMD path used by the dry-run records) so the
recorded baselines stay reproducible.

Layout contract (matches sharding.rules 'expert' mode):
  * x:        (B, T, d)  sharded P(batch_axes, None, None)
  * router:   (d, E)     replicated
  * w_gate/up:(E, d, f)  sharded P('model', None, None)
  * w_down:   (E, f, d)  sharded P('model', None, None)
Requires n_experts % model_axis == 0 and (B_loc*T) % model_axis == 0.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..config.base import ModelConfig
from ..sharding.rules import batch_axes
from .moe import _positions_in_expert


def make_expert_parallel_moe(cfg: ModelConfig, mesh):
    mo = cfg.moe
    ep = mesh.shape["model"]
    assert mo.n_experts % ep == 0, (mo.n_experts, ep)
    e_loc = mo.n_experts // ep
    b_axes = batch_axes(mesh)

    def local_moe(x, router, wg, wu, wd):
        # x: (B_loc, T, d) — replicated over 'model'; take this shard's slice
        Bl, T, d = x.shape
        n_all = Bl * T
        assert n_all % ep == 0, (n_all, ep)
        n = n_all // ep
        me = jax.lax.axis_index("model")
        xf = jax.lax.dynamic_slice_in_dim(x.reshape(n_all, d), me * n, n, 0)

        logits = (xf @ router.astype(x.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, -1)
        gate, ids = jax.lax.top_k(probs, mo.top_k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        cap = max(1, int(math.ceil(n * mo.top_k / mo.n_experts
                                   * mo.capacity_factor)))
        pos = _positions_in_expert(ids.reshape(-1),
                                   mo.n_experts).reshape(n, mo.top_k)
        keep = pos < cap

        buf = jnp.zeros((mo.n_experts, cap, d), x.dtype)
        for s in range(mo.top_k):
            src = jnp.where(keep[:, s, None], xf, 0)
            buf = buf.at[ids[:, s], jnp.where(keep[:, s], pos[:, s], cap)
                         ].add(src, mode="drop")

        # dispatch a2a over 'model': token-shards -> expert-shards
        buf = buf.reshape(ep, e_loc, cap, d)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0)
        # now (ep, e_loc, cap, d): [src_shard, local_expert, slot, d]
        buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, d)

        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(x.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(x.dtype))
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                         wd.astype(x.dtype))

        # combine a2a: inverse exchange back to token-shards
        out = out.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3)
        out = jax.lax.all_to_all(out, "model", split_axis=0, concat_axis=0)
        out = out.reshape(mo.n_experts, cap, d)

        y = jnp.zeros((n, d), x.dtype)
        for s in range(mo.top_k):
            contrib = out[ids[:, s], jnp.minimum(pos[:, s], cap - 1)]
            w = jnp.where(keep[:, s], gate[:, s], 0).astype(x.dtype)
            y = y + contrib * w[:, None]
        # restore the replicated-over-'model' activation layout
        y_all = jax.lax.all_gather(y, "model", axis=0, tiled=True)
        return y_all.reshape(Bl, T, d)

    shmap = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(P(b_axes, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(b_axes, None, None),
        check_vma=False,
    )

    def apply(p: dict, prefix: str, x: jax.Array):
        return shmap(x, p[prefix + "router"], p[prefix + "w_gate"],
                     p[prefix + "w_up"], p[prefix + "w_down"])

    return apply
