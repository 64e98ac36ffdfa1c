"""DLRM — the paper's own architecture (bottom MLP + EmbeddingBags + feature
interaction + top MLP), int8-quantized with ABFT end to end.

This model is the native home of the two protected operators: every GEMM
(the MLPs' and DLRMv2's cross network's) runs Algorithm 1, every table
lookup runs Algorithm 2.  Two interactions (:class:`DlrmExtras`):

- ``dot`` (DLRMv1, the paper's Table I): the upper triangle of the Gram
  matrix of [bottom output, pooled tables];
- ``dcnv2`` (MLPerf's DLRMv2): a low-rank cross network over x_0 =
  [bottom output, pooled tables], x_{l+1} = x_0 ⊙ (W_l (V_l x_l) + b_l) +
  x_l, its element-wise terms in the compute dtype.

Tables of equal rows and pool are stacked ``[T, rows, d]`` and looked up
under ``vmap``; tables that differ (DLRMv2) are held one after the other
in one table and looked up in one call, each with its own pool.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.dlrm import DlrmExtras
from repro.core import policy
from repro.layers.common import Ctx
from repro.layers.embedding import (embedding_bag_fwd, init_embedding_bag,
                                    init_embedding_bags, table_offsets)
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


def _init_cross(key, ex: DlrmExtras, quant, dtype):
    """The cross layers stacked: per layer V (x_0 width -> rank, no bias)
    and W (rank -> x_0 width, with bias), each from one of two keys split
    from the layer's (one of ``cross_layers`` split from ``key``)."""
    def layer(k):
        kv, kw = jax.random.split(k)
        return {"v": maybe_qlinear_init(kv, ex.inter_dim, ex.cross_rank,
                                        ("embed", "mlp"), quant, dtype,
                                        bias=False),
                "w": maybe_qlinear_init(kw, ex.cross_rank, ex.inter_dim,
                                        ("mlp", "embed"), quant, dtype)}

    # one traced layer, not one per layer (each takes seconds to compile)
    stacked = jax.lax.map(layer, jax.random.split(key, ex.cross_layers))
    return jax.tree.map(lambda p: LogicalParam(p.value, ("layers",) + p.axes),
                        stacked, is_leaf=is_lp)


def init_dlrm(key, ex: DlrmExtras, quant: bool = True, dtype=jnp.float32,
              table_rows: int | None = None):
    """Weights from ``key`` split three ways (bottom MLP, top MLP, tables;
    four with the cross network last).  ``table_rows`` overrides the rows
    of equal tables."""
    dcn = ex.interaction == "dcnv2"
    k1, k2, k3, *k4 = jax.random.split(key, 4 if dcn else 3)
    bottom = _init_mlp_stack(k1, (ex.n_dense,) + ex.bottom_mlp, quant, dtype)
    top = _init_mlp_stack(k2, (ex.inter_dim,) + ex.top_mlp, quant, dtype)
    if ex.ragged:
        tables = init_embedding_bags(k3, ex.rows, ex.emb_dim)
    else:
        rows = table_rows or ex.table_rows
        # one table at a time: peak init memory is the stacked tables plus
        # ONE table's temporaries (a vmap over all tables holds every
        # table's)
        tables = jax.lax.map(
            lambda k: init_embedding_bag(k, rows, ex.emb_dim),
            jax.random.split(k3, ex.n_tables))
        tables = jax.tree.map(
            lambda p: LogicalParam(p.value, ("tables",) + p.axes), tables,
            is_leaf=is_lp)
    params = {"bottom": bottom, "top": top, "tables": tables}
    if dcn:
        params["cross"] = _init_cross(k4[0], ex, quant, dtype)
    return params


def _mlp_stack(layers, x, ctx, final_relu=False, name="mlp"):
    rep = policy.empty_report()
    for i, p in enumerate(layers):
        x, r = apply_linear(p, x, ctx, name=f"{name}.{i}")
        rep = policy.merge_reports(rep, r)
        if i < len(layers) - 1 or final_relu:
            x = jax.nn.relu(x.astype(jnp.float32)).astype(x.dtype)
    return x, rep


def _lookup(params, indices, ctx: Ctx, ex: DlrmExtras):
    """Every table's pooled bags -> ([T, B, e], compute dtype; report)."""
    if not ex.ragged:
        embs, reps = jax.vmap(lambda tp, idx: embedding_bag_fwd(
            tp, idx, ctx))(params["tables"], indices)
        # vmapped FaultReport: reduce counts over the table axis
        return embs, jax.tree.map(lambda x: jnp.sum(x), reps)
    # [B, Σ pool] table-local ids -> per table [B, pool_t] rows of the
    # concatenated table
    offs, bags, col = table_offsets(ex.rows), [], 0
    for t, pool in enumerate(ex.pools):
        ids = indices[:, col:col + pool]
        bags.append(jnp.where(ids >= 0, ids + offs[t], -1))
        col += pool
    return embedding_bag_fwd(params["tables"], tuple(bags), ctx)


def _cross(layers, x0, ctx):
    """DLRMv2's low-rank cross network over x0 [B, D] (compute dtype)."""
    rep, x = policy.empty_report(), x0
    for i in range(layers["v"]["w_packed"].shape[0]):
        p = jax.tree.map(lambda a: a[i], layers)
        y, r1 = apply_linear(p["v"], x, ctx, name=f"cross.{i}.v")
        y, r2 = apply_linear(p["w"], y, ctx, name=f"cross.{i}.w")
        x = x0 * y + x
        rep = policy.merge_reports(rep, r1, r2)
    return x, rep


def dlrm_forward(params, dense, indices, ctx: Ctx, ex: DlrmExtras,
                 weights=None) -> Tuple[jax.Array, policy.FaultReport]:
    """dense [B, n_dense] f32; indices int32 (−1 pad): [n_tables, B, pool]
    for equal tables, [B, Σ pooling] table-local ids, table after table,
    for ragged ones (:attr:`DlrmExtras.ragged`).

    Returns (logit [B], report)."""
    bot, r1 = _mlp_stack(params["bottom"], dense.astype(ctx.compute_dtype),
                         ctx, final_relu=True, name="bottom")  # [B, emb]
    embs, table_rep = _lookup(params, indices, ctx, ex)        # [T, B, e]
    if ex.interaction == "dcnv2":
        b = bot.shape[0]
        x0 = jnp.concatenate([bot, embs.transpose(1, 0, 2).reshape(b, -1)],
                             axis=-1).astype(ctx.compute_dtype)
        z, r_int = _cross(params["cross"], x0, ctx)
    else:
        feats = jnp.concatenate([bot[None].astype(jnp.float32),
                                 embs.astype(jnp.float32)], axis=0)  # [F,B,e]
        f = feats.transpose(1, 0, 2)                            # [B,F,e]
        gram = jnp.einsum("bfe,bge->bfg", f, f)                 # [B,F,F]
        iu = jnp.triu_indices(f.shape[1], k=1)
        inter = gram[:, iu[0], iu[1]]                           # [B,F(F-1)/2]
        z = jnp.concatenate([bot.astype(jnp.float32), inter], axis=-1)
        r_int = policy.empty_report()
    logit, r2 = _mlp_stack(params["top"], z.astype(ctx.compute_dtype), ctx,
                           name="top")
    return logit[:, 0], policy.merge_reports(r1, table_rep, r_int, r2)
