"""Pallas TPU kernel: quantized EmbeddingBag with fused ABFT row-sum.

TPU-native analogue of FBGEMM's prefetching EB: bag indices are
*scalar-prefetched* (``PrefetchScalarGridSpec``) so the row group of the
next lookup is known to the DMA engine ahead of the grid step; each step
streams one tile-aligned group of ``G`` table rows HBM→VMEM, selects the
wanted row in VMEM, dequantizes it (α_i, β_i), and accumulates both the bag
vector and its scalar sum — the left side of Eq. (5) — in the same pass.

Why a row *group*: an int8 table lives in HBM in 8-row tiles, and Mosaic
refuses a 1-row block or DMA out of it.  Fetching the aligned 8-row group
that holds the row keeps the table's layout (and its HBM bytes) unchanged.

Tables are a grid axis: the kernel takes a stacked ``[T, rows, d]`` table,
grid = (T, bags, pool).  A single-table call is ``T = 1``; under ``vmap``
over tables (the DLRM forward) a custom batching rule folds the mapped
axis into ``T`` instead of letting Pallas loop over tables — the loop
slices (copies) a whole table per iteration.

Outputs: ``R [bags, d] f32`` and ``rsum [bags] f32`` (Σ_j R[b, j]).  The
Eq. (5) comparison against the gathered table row-sums is O(bags·pool)
and happens in :func:`repro.core.verify_bags`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows per fetched group: one HBM tile of an int8 [rows, d] table.
ROW_GROUP = 8


def _kernel(idx_ref, coef_ref, rows_ref, r_ref, rsum_ref, acc_ref, *,
            pool: int, group: int):
    b = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when((b == 0) & (p == 0))
    def _zero_out():
        r_ref[...] = jnp.zeros_like(r_ref)
        rsum_ref[...] = jnp.zeros_like(rsum_ref)

    @pl.when(p == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = pl.program_id(0)
    alpha = coef_ref[0, b, p]
    beta = coef_ref[1, b, p]
    w = coef_ref[2, b, p]
    grp = rows_ref[...].astype(jnp.float32)                 # [group, d]
    pick = jax.lax.broadcasted_iota(jnp.int32, grp.shape, 0) \
        == idx_ref[t, b, p] % group
    row = jnp.sum(jnp.where(pick, grp, 0.0), axis=0, keepdims=True)
    acc_ref[...] += w * (alpha * row + beta)

    @pl.when(p == pool - 1)
    def _flush():
        acc = acc_ref[...]
        r_ref[...] = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, r_ref.shape, 0) == b,
            acc, r_ref[...])
        rsum_ref[...] = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, rsum_ref.shape, 0) == b,
            jnp.sum(acc, axis=1, keepdims=True), rsum_ref[...])


def _eb_tables_call(table_q, coef, idx, *, interpret: bool):
    """table_q int8 [T, rows, d]; coef f32 [T, 3, bags, pool] (α, β, w per
    slot); idx int32 [T, bags, pool] (non-negative) -> (R [T, bags, d],
    rsum [T, bags])."""
    n_tables, bags, pool = idx.shape
    rows, d = table_q.shape[1:]
    group = min(ROW_GROUP, rows)
    bags_p = -(-bags // 8) * 8
    out = lambda t, b, p, idx_ref: (t, 0, 0)               # noqa: E731
    r, rsum = pl.pallas_call(
        functools.partial(_kernel, pool=pool, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tables, bags, pool),
            in_specs=[
                pl.BlockSpec((None, 3, bags, pool),
                             lambda t, b, p, idx_ref: (t, 0, 0, 0),
                             memory_space=pltpu.SMEM),
                # the aligned row group holding this slot's row, addressed
                # by the prefetched index — the TPU analogue of software
                # prefetch
                pl.BlockSpec(
                    (None, group, d),
                    lambda t, b, p, idx_ref: (t, idx_ref[t, b, p] // group,
                                              0)),
            ],
            out_specs=[pl.BlockSpec((None, bags_p, d), out),
                       pl.BlockSpec((None, bags_p, 128), out)],
            scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_tables, bags_p, d), jnp.float32),
            jax.ShapeDtypeStruct((n_tables, bags_p, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(idx, coef, table_q)
    return r[:, :bags], rsum[:, :bags, 0]


def _fold_tables(interpret: bool):
    """``_eb_tables_call`` with a batching rule that maps ``vmap`` onto the
    kernel's table axis."""

    @jax.custom_batching.custom_vmap
    def call(table_q, coef, idx):
        return _eb_tables_call(table_q, coef, idx, interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, table_q, coef, idx):
        table_b, coef_b, idx_b = in_batched
        if not coef_b:
            coef = jnp.broadcast_to(coef, (axis_size,) + coef.shape)
        if not idx_b:
            idx = jnp.broadcast_to(idx, (axis_size,) + idx.shape)
        n_tables, _, bags, pool = coef.shape[1:]
        if table_b:
            # [V, T, ...] -> [V*T, ...]: a free reshape of the stacked table
            r, rsum = call(table_q.reshape((-1,) + table_q.shape[2:]),
                           coef.reshape((-1,) + coef.shape[2:]),
                           idx.reshape((-1,) + idx.shape[2:]))
            return ((r.reshape(axis_size, n_tables, bags, -1),
                     rsum.reshape(axis_size, n_tables, bags)), (True, True))
        # one table set shared by every mapped member (the campaign's
        # eb_pallas clean trials vmap keys over one table): stack them as
        # bags
        coef = coef.transpose(1, 2, 0, 3, 4).reshape(
            n_tables, 3, axis_size * bags, pool)
        idx = idx.transpose(1, 0, 2, 3).reshape(
            n_tables, axis_size * bags, pool)
        r, rsum = call(table_q, coef, idx)
        r = r.reshape(n_tables, axis_size, bags, -1).transpose(1, 0, 2, 3)
        rsum = rsum.reshape(n_tables, axis_size, bags).transpose(1, 0, 2)
        return (r, rsum), (True, True)

    return call


_FOLDED = {False: _fold_tables(False), True: _fold_tables(True)}


@functools.partial(jax.jit, static_argnames=("interpret",))
def abft_eb_pallas(table_q: jax.Array, alphas: jax.Array, betas: jax.Array,
                   indices: jax.Array, weights: jax.Array | None = None, *,
                   interpret: bool = False):
    """Gather-and-sum with fused RSum. Returns ``(R [bags,d], rsum [bags])``.

    table_q int8 [rows, d]; alphas/betas f32 [rows]; indices int32
    [bags, pool] (−1 padded); weights f32 [bags, pool] or None.
    """
    valid = indices >= 0
    safe_idx = jnp.where(valid, indices, 0).astype(jnp.int32)
    w = jnp.ones_like(alphas[safe_idx]) if weights is None else weights
    w = jnp.where(valid, w, 0.0)
    # [3, bags, pool]: per-slot (alpha, beta, weight) — gathered by XLA
    # (O(bags*pool) — negligible vs the O(bags*pool*d) row traffic).
    coef = jnp.stack([alphas[safe_idx], betas[safe_idx], w])
    r, rsum = _FOLDED[interpret](table_q[None], coef[None], safe_idx[None])
    return r[0], rsum[0]
