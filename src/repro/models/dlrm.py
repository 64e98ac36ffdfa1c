"""DLRM — the paper's own architecture (bottom MLP + EmbeddingBags +
pairwise interaction + top MLP), int8-quantized with ABFT end to end.

This model is the native home of the two protected operators: every MLP
GEMM runs Algorithm 1, every table lookup runs Algorithm 2.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.dlrm import DlrmExtras
from repro.core import policy
from repro.layers.common import Ctx
from repro.layers.embedding import embedding_bag_fwd, init_embedding_bag
from repro.layers.linear import apply_linear, maybe_qlinear_init
from repro.sharding import LogicalParam, is_lp


def _init_mlp_stack(key, dims, quant, dtype, in_axis="embed",
                    out_axis="mlp"):
    ks = jax.random.split(key, len(dims) - 1)
    return [maybe_qlinear_init(ks[i], dims[i], dims[i + 1],
                               (in_axis, out_axis) if i % 2 == 0
                               else (out_axis, in_axis),
                               quant, dtype)
            for i in range(len(dims) - 1)]


def init_dlrm(key, ex: DlrmExtras, quant: bool = True, dtype=jnp.float32,
              table_rows: int | None = None):
    rows = table_rows or ex.table_rows
    k1, k2, k3 = jax.random.split(key, 3)
    bottom = _init_mlp_stack(k1, (ex.n_dense,) + ex.bottom_mlp, quant, dtype)
    n_feat = ex.n_tables + 1
    inter_dim = ex.emb_dim + n_feat * (n_feat - 1) // 2
    top = _init_mlp_stack(k2, (inter_dim,) + ex.top_mlp, quant, dtype)
    # one table at a time: peak init memory is the stacked tables plus ONE
    # table's temporaries (a vmap over all tables holds every table's)
    tables = jax.lax.map(
        lambda k: init_embedding_bag(k, rows, ex.emb_dim),
        jax.random.split(k3, ex.n_tables))
    tables = jax.tree.map(
        lambda p: LogicalParam(p.value, ("tables",) + p.axes), tables,
        is_leaf=is_lp)
    return {"bottom": bottom, "top": top, "tables": tables}


def _mlp_stack(layers, x, ctx, final_relu=False, name="mlp"):
    rep = policy.empty_report()
    for i, p in enumerate(layers):
        x, r = apply_linear(p, x, ctx, name=f"{name}.{i}")
        rep = policy.merge_reports(rep, r)
        if i < len(layers) - 1 or final_relu:
            x = jax.nn.relu(x.astype(jnp.float32)).astype(x.dtype)
    return x, rep


def dlrm_forward(params, dense, indices, ctx: Ctx, ex: DlrmExtras,
                 weights=None) -> Tuple[jax.Array, policy.FaultReport]:
    """dense [B, n_dense] f32; indices [n_tables, B, pool] int32 (−1 pad).

    Returns (logit [B], report)."""
    b = dense.shape[0]
    bot, r1 = _mlp_stack(params["bottom"], dense.astype(ctx.compute_dtype),
                         ctx, final_relu=True, name="bottom")  # [B, emb]

    def one_table(tp, idx):
        r, rep = embedding_bag_fwd(tp, idx, ctx)
        return r, rep

    embs, table_reps = jax.vmap(one_table)(params["tables"], indices)
    # vmapped FaultReport: reduce counts over the table axis
    table_rep = jax.tree.map(lambda x: jnp.sum(x), table_reps)

    feats = jnp.concatenate([bot[None].astype(jnp.float32),
                             embs.astype(jnp.float32)], axis=0)  # [F,B,e]
    f = feats.transpose(1, 0, 2)                                # [B,F,e]
    gram = jnp.einsum("bfe,bge->bfg", f, f)                     # [B,F,F]
    iu = jnp.triu_indices(f.shape[1], k=1)
    inter = gram[:, iu[0], iu[1]]                               # [B,F(F-1)/2]
    z = jnp.concatenate([bot.astype(jnp.float32), inter], axis=-1)
    logit, r2 = _mlp_stack(params["top"], z.astype(ctx.compute_dtype), ctx,
                           name="top")
    return logit[:, 0], policy.merge_reports(r1, table_rep, r2)
