"""Pallas TPU kernel: quantized EmbeddingBag with fused ABFT row-sum.

TPU-native analogue of FBGEMM's prefetching EB.  The table stays in HBM
(``memory_space=pl.ANY``); the bag indices are *scalar-prefetched*
(``PrefetchScalarGridSpec``) so every row address of a grid step is known
up front, and the step keeps a ring of ``RING`` row DMAs in flight: it
starts the first ``RING - 1`` fetches, then for each slot (one bag
position) starts the fetch ``RING - 1`` slots ahead, waits on this slot's
own, dequantizes the row (α_i, β_i, weight) and adds it to the bag's
accumulator.  The bag vector and its scalar sum — the left side of
Eq. (5) — come out of the same pass.

Why a row *group*: an int8 table lives in HBM in (8, 128) tiles, and
Mosaic refuses a DMA that cuts a tile.  A table whose d is a multiple of
128 is row-major, and each fetch copies the aligned 8-row group that holds
the row.  XLA stores a table with d < 128 d-major (rows on lanes, so no
lane padding); the kernel reads it as ``[T, d, rows]``, a transpose that
moves no bytes, and each fetch copies the ``(d, 128)`` block that holds
the row.  Either way the table keeps its layout and its HBM bytes, and no
table-sized temporary appears.  The accumulator is the whole fetched group
in f32, the wanted row picked by a masked select, so the reduction over
the group runs once per bag, not once per row.  Rows past the table's
last whole group come from a one-group copy of that tail.

Grid = (tables, bag blocks).  A block holds the bags of about
``STEP_SLOTS`` slots, so that step overhead is paid per block and not per
row; its size follows from the call's ``(bags, pool, d)`` (a pool of 1
puts hundreds of bags in a step).  A single-table call is ``T = 1``;
under ``vmap`` over tables (the DLRM forward) a custom batching rule folds
the mapped axis into ``T`` instead of letting Pallas loop over tables —
the loop slices (copies) a whole table per iteration.

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

#: rows per fetched group of a row-major table: one HBM tile of an int8
#: [rows, d] table whose d is a multiple of 128.
ROW_GROUP = 8
#: rows per fetched block of a d-major table (d < 128): one lane tile.
LANE_GROUP = 128
#: row DMAs a grid step keeps in flight (its VMEM ring of row groups); a
#: power of two.
RING = 32
#: slots in one iteration of a bag's slot loop, unrolled by hand (Mosaic
#: unrolls a ``fori_loop`` only whole) so that their scalar work overlaps:
#: 8 ran 10–14% faster than 1 on a TPU v5e.
UNROLL = 8
#: slots (bags x pool) a grid step aims to cover.
STEP_SLOTS = 1024
#: VMEM bytes one step's output blocks may take.
OUT_VMEM = 2 << 20


def _bag_block(bags: int, pool: int, d: int) -> tuple[int, int]:
    """(bags per grid step, number of bag blocks) for this call's shapes."""
    cap = min(STEP_SLOTS // pool, OUT_VMEM // (4 * (d + 128)))
    if bags <= cap:
        return bags, 1
    # several blocks: each block's rows a multiple of the 8-row out tile
    cap = max(8, cap // 8 * 8)
    n_blocks = -(-bags // cap)
    per = -(-bags // n_blocks)
    return -(-per // 8) * 8, n_blocks


def _kernel(idx_ref, coef_ref, table_ref, *refs, bags: int, pool: int,
            block: int, n_blocks: int, whole: int, tail: bool, lanes: bool):
    """One (table, bag block) step.  ``lanes``: the table is d-major
    ([T, d, rows]) and each fetch is a ``(d, 128)`` block of rows along
    lanes; else it is row-major and each fetch an ``(8, d)`` row group.
    Rows from ``whole`` on come from ``tail_ref``, one group a table."""
    if tail:
        tail_ref, r_ref, rsum_ref, buf_ref, sem_ref = refs
    else:
        r_ref, rsum_ref, buf_ref, sem_ref = refs
    t = pl.program_id(0)
    j = pl.program_id(1)
    base = (t * n_blocks + j) * (block * pool)
    # the last block may hold fewer bags than ``block``
    n_bags = block if bags % block == 0 else jnp.minimum(block,
                                                         bags - j * block)
    n_slots = n_bags * pool
    unit = buf_ref.shape[1:]
    axis = 1 if lanes else 0                 # the axis a fetch holds rows on
    group = unit[axis]

    def rows_at(start):
        if lanes:
            return table_ref.at[t, :, pl.ds(start, group)]
        return table_ref.at[t, pl.ds(start, group)]

    def dma(src, s):
        k = s & (RING - 1)
        return pltpu.make_async_copy(src, buf_ref.at[k], sem_ref.at[k])

    def fetch(s):
        row = idx_ref[base + s]

        def from_table():
            dma(rows_at(pl.multiple_of(row & -group, group)), s).start()

        if not tail:
            from_table()
            return
        if whole:
            pl.when(row < whole)(from_table)
        pl.when(row >= whole)(lambda: dma(tail_ref.at[t], s).start())

    # every fetch moves one group: any group's descriptor waits for it
    any_group = tail_ref.at[t] if tail else rows_at(0)
    for s in range(min(RING - 1, block * pool)):
        pl.when(s < n_slots)(functools.partial(fetch, s))

    pos = jax.lax.broadcasted_iota(jnp.int32, unit, axis)

    def bag(b, carry):
        def slot(p, acc_beta):
            acc, beta = acc_beta
            s = b * pool + p
            # refill the buffer the previous slot consumed, then wait
            pl.when(s + RING - 1 < n_slots)(
                functools.partial(fetch, s + RING - 1))
            dma(any_group, s).wait()
            grp = buf_ref[s & (RING - 1)].astype(jnp.float32)
            pick = pos == (idx_ref[base + s] & (group - 1))
            w = coef_ref[2, s]
            # w·(α·row + β): the α term in the group's lanes or sublanes,
            # the β term a scalar added to every element at the flush
            return (acc + jnp.where(pick, (w * coef_ref[0, s]) * grp, 0.0),
                    beta + w * coef_ref[1, s])

        def slots(p0, n, acc_beta):
            for i in range(n):
                acc_beta = slot(p0 + i, acc_beta)
            return acc_beta

        acc_beta = jax.lax.fori_loop(
            0, pool // UNROLL, lambda i, c: slots(i * UNROLL, UNROLL, c),
            (jnp.zeros(unit, jnp.float32), jnp.float32(0)))
        acc, beta = slots(pool // UNROLL * UNROLL, pool % UNROLL, acc_beta)
        if lanes:
            acc = acc.T
        r = jnp.sum(acc, axis=0, keepdims=True) + beta           # [1, d]
        r_ref[pl.ds(b, 1), :] = r
        rsum_ref[pl.ds(b, 1), :] = jnp.broadcast_to(
            jnp.sum(r, axis=1, keepdims=True), (1, rsum_ref.shape[-1]))
        return carry

    jax.lax.fori_loop(0, n_bags, bag, 0)


def _eb_tables_call(table_q, coef, idx, *, interpret: bool):
    """table_q int8 [T, rows, d]; coef f32 [T, 3, bags, pool] (α, β, w per
    slot); idx int32 [T, bags, pool] (non-negative) -> (R [T, bags, d],
    rsum [T, bags])."""
    n_tables, bags, pool = idx.shape
    rows, d = table_q.shape[1:]
    block, n_blocks = _bag_block(bags, pool, d)
    bags_p = block * n_blocks
    if bags_p > bags:
        pad = ((0, 0), (0, bags_p - bags), (0, 0))
        idx = jnp.pad(idx, pad)
        coef = jnp.pad(coef, ((0, 0),) + pad)
    # [T, blocks, 3, block*pool]: one SMEM block of coefficients a step
    coef = coef.reshape(n_tables, 3, n_blocks, block * pool) \
        .transpose(0, 2, 1, 3)
    # XLA keeps an int8 table with d < 128 d-major in HBM (rows on lanes, no
    # lane padding): read it as [T, d, rows], which is the same bytes
    lanes = d < LANE_GROUP
    group = LANE_GROUP if lanes else ROW_GROUP
    unit = (d, group) if lanes else (group, d)
    as_stored = functools.partial(jnp.swapaxes, axis1=1, axis2=2) if lanes \
        else (lambda x: x)
    whole = rows // group * group
    args = [idx.reshape(-1), coef, as_stored(table_q)]
    in_specs = [
        pl.BlockSpec((None, None, 3, block * pool),
                     lambda t, j, idx_ref: (t, j, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if whole < rows:
        # the rows past the last whole group, zero-padded to one group
        args.append(as_stored(jnp.pad(
            table_q[:, whole:], ((0, 0), (0, whole + group - rows), (0, 0)))))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out = lambda t, j, idx_ref: (t, j, 0)                  # noqa: E731
    r, rsum = pl.pallas_call(
        functools.partial(_kernel, bags=bags, pool=pool, block=block,
                          n_blocks=n_blocks, whole=whole, tail=whole < rows,
                          lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tables, n_blocks),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((None, block, d), out),
                       pl.BlockSpec((None, block, 128), out)],
            scratch_shapes=[pltpu.VMEM((RING,) + unit, jnp.int8),
                            pltpu.SemaphoreType.DMA((RING,))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_tables, bags_p, d), jnp.float32),
            jax.ShapeDtypeStruct((n_tables, bags_p, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*args)
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
