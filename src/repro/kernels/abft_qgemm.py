"""Pallas TPU kernel: int8 ABFT GEMM with fused checksum verification.

Computes ``C[int32] = A[int8] @ B'[int8]`` where ``B' = [B | checksum-block]``
(:func:`repro.core.abft_gemm.pack_encoded_b`), and verifies Eq. (3b) row-wise
*in the epilogue* while C tiles are still in VMEM.

Tiling (DESIGN.md §3):
  grid = (M/bm, N'/bn, K/bk), K innermost (accumulation), then N, then M.
  * ``acc``     VMEM scratch [bm, bn] int32 — MXU accumulator across K tiles.
  * ``rowsum``  VMEM scratch [bm]    int32 — running ``Σ_j C[i,j] mod 127``
                across N tiles of the same M row-block (grid order makes N
                sequential for fixed M, so the scratch carries across tiles).
  * The final N tile group is the 128-lane checksum block: lane 0 holds
    ``A @ S_B``; the epilogue compares it (mod 127) against ``rowsum`` and
    writes the per-row error flags.

Per-element ``mod`` before the row reduction keeps the verify exact for any N
(no int32 overflow), per DESIGN.md §3.

uint8 activations ride a zero-point path: the wrapper shifts ``A_u`` to
``A_s = A_u - 128`` (int8, a bit-xor), the MXU runs signed, and the epilogue
adds ``128 · Σ_k B'[k, j]`` back per column from the ``bcol`` scratch —
**before** the rowsum/verify, so the flags are bit-identical to the unsigned
reference path (128 ≡ 1 mod 127, so a clean checksum block stays clean and a
corrupted one trips exactly when the reference trips).  The correction costs
zero extra HBM traffic: ``bcol`` accumulates from the B' tiles already in
VMEM for the MXU step.

``with_colcheck=True`` additionally emits the Eq.-1 expected column sums
``colsum(A) @ B'`` in the same pass — an independent per-tile matvec over the
A/B' tiles (NOT a reduction of the C tiles: an accumulator fault must show up
as a *disagreement* between C's column sums and this check, which a fold of C
would cancel by construction).

The verify costs zero extra HBM traffic: the paper's CPU version re-reads C
from cache (O(mn) reads); here the reduction happens on the tile the MXU just
produced.  This is the kernel-level beyond-paper win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import LANE, MOD


def _kernel(a_ref, bp_ref, *refs, n_tiles: int, k_tiles: int, m_tiles: int,
            mod: int, zero_point: int, valid_m: int, with_colcheck: bool,
            bn: int, col_digits: int):
    # refs = outputs (c, err[, col]) then scratches (acc, rowsum[, bcol]
    # [, colacc]) — the optional ones exist only when their static flag is
    # set, so unpack by the same flags.
    if with_colcheck:
        c_ref, err_ref, col_ref = refs[:3]
        scratch = refs[3:]
    else:
        c_ref, err_ref = refs[:2]
        scratch = refs[2:]
    acc_ref, rowsum_ref = scratch[:2]
    scratch = scratch[2:]
    if zero_point:
        bcol_ref, scratch = scratch[0], scratch[1:]
    if with_colcheck:
        colacc_ref = scratch[0]

    i = pl.program_id(0)
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if zero_point:
            bcol_ref[...] = jnp.zeros_like(bcol_ref)

    @pl.when((j == 0) & (kk == 0))
    def _zero_row_state():
        rowsum_ref[...] = jnp.zeros_like(rowsum_ref)
        err_ref[...] = jnp.zeros_like(err_ref)

    if with_colcheck:
        @pl.when((i == 0) & (kk == 0))
        def _zero_colacc():
            colacc_ref[0, pl.ds(j * bn, bn)] = jnp.zeros((bn,), jnp.int32)

    # MXU step: int8 x int8 -> int32.
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], bp_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    if zero_point:
        # per-column Σ_k B'[k, j] for the epilogue's zero-point correction
        bcol_ref[...] += jnp.sum(bp_ref[...].astype(jnp.int32), axis=0)

    if with_colcheck:
        # Eq.-1 colsum matvec fused into the same pass: colsum of the A
        # tile (zero-padded rows contribute 0) times the B' tile.  Runs on
        # the tiles already in VMEM — no extra HBM reads.  The MXU takes
        # no int32 operands, so the colsum is split into base-128 int8
        # digits, each an exact int8 x int8 -> int32 row-vector dot.
        asum = jnp.sum(a_ref[...].astype(jnp.int32), axis=0, keepdims=True)
        contrib = jnp.zeros((1, bn), jnp.int32)
        for d in range(col_digits):
            digit = asum if d == col_digits - 1 else asum & 127
            contrib += jax.lax.dot_general(
                digit.astype(jnp.int8), bp_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32) << (7 * d)
            asum = asum >> 7
        contrib = contrib[0]
        colacc_ref[0, pl.ds(j * bn, bn)] += contrib
        if zero_point:
            # the unsigned colsum is colsum(A_s) + 128·m; add the constant
            # term once per (j, kk) — it does not depend on the A row tile
            @pl.when(i == 0)
            def _colacc_zp():
                colacc_ref[0, pl.ds(j * bn, bn)] += (
                    zero_point * valid_m
                    * jnp.sum(bp_ref[...].astype(jnp.int32), axis=0))

    @pl.when(kk == k_tiles - 1)
    def _epilogue():
        tile = acc_ref[...]
        if zero_point:
            # restore the unsigned product before verify: the flags must
            # be computed on C_u = C_s + 128·Σ_k B', not on the shifted
            # intermediate, or uint8 detection would diverge from the
            # reference path
            tile = tile + zero_point * bcol_ref[...][None, :]
        c_ref[...] = tile

        @pl.when(j < n_tiles - 1)
        def _accumulate_rowsum():
            # per-element mod bounds the row sum by 126*bn (no overflow).
            rowsum_ref[...] = (rowsum_ref[...]
                               + jnp.sum(tile % mod, axis=1)) % mod

        @pl.when(j == n_tiles - 1)
        def _verify():
            check = tile[:, 0] % mod          # lane 0 = A @ S_B
            bad = rowsum_ref[...] != check
            err_ref[...] = bad.astype(jnp.int32)[:, None]

        if with_colcheck:
            @pl.when(i == m_tiles - 1)
            def _flush_col():
                col_ref[...] = colacc_ref[0:1, pl.ds(j * bn, bn)]


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "mod", "interpret", "with_colcheck"))
def abft_qgemm_pallas(a_q: jax.Array, b_packed: jax.Array, *,
                      bm: int = 128, bn: int = 128, bk: int = 128,
                      mod: int = MOD, interpret: bool = False,
                      with_colcheck: bool = False):
    """Run the fused ABFT GEMM. Returns ``(C [m,n] int32, err_rows [m] i32)``,
    plus the Eq.-1 expected column sums (``int32 [n]``) when
    ``with_colcheck=True``.

    ``a_q``: uint8 or int8 [m, k] (activations; uint8 rides the zero-point
    path and produces bit-identical C/flags to the reference);
    ``b_packed``: int8 [k, n + LANE] from :func:`pack_encoded_b`.
    Shapes are padded up to tile multiples internally; zero padding is
    checksum-neutral (zero rows/cols contribute 0 to every sum).
    """
    m, k = a_q.shape
    k2, n_packed = b_packed.shape
    assert k == k2, (a_q.shape, b_packed.shape)
    n = n_packed - LANE
    assert n >= 1
    assert LANE % bn == 0 or bn % LANE == 0, "checksum block must tile evenly"
    if b_packed.dtype != jnp.int8:
        raise TypeError(f"b_packed must be int8 (pack_encoded_b output), "
                        f"got {b_packed.dtype}")
    if a_q.dtype == jnp.int8:
        zero_point = 0
    elif a_q.dtype == jnp.uint8:
        # A_u = A_s + 128 with A_s = (A_u ^ 0x80) as int8 — exact, and the
        # epilogue adds 128·Σ_k B' back per column.  A bare astype would
        # silently reinterpret values >= 128 as negative.
        zero_point = 128
        a_q = (a_q ^ jnp.uint8(0x80)).astype(jnp.int8)
    else:
        raise TypeError(f"a_q must be int8 or uint8, got {a_q.dtype}")

    mp = -(-m // bm) * bm
    kp = -(-k // bk) * bk
    np_ = -(-n // bn) * bn
    cs_width = max(LANE, bn)  # checksum block padded to a whole tile group

    a_pad = jnp.zeros((mp, kp), jnp.int8).at[:m, :k].set(a_q)
    bp_pad = jnp.zeros((kp, np_ + cs_width), jnp.int8)
    bp_pad = bp_pad.at[:k, :n].set(b_packed[:, :n])
    bp_pad = bp_pad.at[:k, np_:np_ + LANE].set(b_packed[:, n:])

    n_tiles_c = np_ // bn               # tiles holding real C columns
    cs_tiles = cs_width // bn           # tiles holding the checksum block
    n_tiles = n_tiles_c + cs_tiles
    k_tiles = kp // bk
    m_tiles = mp // bm
    grid = (m_tiles, n_tiles, k_tiles)

    # NOTE: when bn > LANE the checksum block is one tile (cs_tiles == 1);
    # when bn < LANE it spans several tiles but lane 0 of the *first* of them
    # carries the checksum, so we treat tile index n_tiles_c as "the" verify
    # tile and ignore the trailing zero tiles.
    # base-128 digits of a bm-row int8 column sum (|sum| <= 128*bm), the
    # top one signed: enough that the top digit fits int8
    col_digits = 1
    while 128 ** (col_digits - 1) < bm:
        col_digits += 1
    kernel = functools.partial(
        _kernel, n_tiles=n_tiles_c + 1, k_tiles=k_tiles, m_tiles=m_tiles,
        mod=mod, zero_point=zero_point, valid_m=m,
        with_colcheck=with_colcheck, bn=bn, col_digits=col_digits)

    out_specs = [
        pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((mp, n_tiles * bn), jnp.int32),
        jax.ShapeDtypeStruct((mp, 1), jnp.int32),
    ]
    scratch_shapes = [
        pltpu.VMEM((bm, bn), jnp.int32),
        pltpu.VMEM((bm,), jnp.int32),
    ]
    # the col output block (0, j) is revisited across M tiles, so the M
    # dimension loses its "parallel" independence when the check is fused
    m_semantics = "parallel"
    if zero_point:
        scratch_shapes.append(pltpu.VMEM((bn,), jnp.int32))
    if with_colcheck:
        out_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        out_shape.append(
            jax.ShapeDtypeStruct((1, n_tiles * bn), jnp.int32))
        scratch_shapes.append(pltpu.VMEM((1, n_tiles * bn), jnp.int32))
        m_semantics = "arbitrary"

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(m_semantics, "arbitrary", "arbitrary")),
        interpret=interpret,
    )(a_pad, bp_pad)

    if with_colcheck:
        c_full, err, col = outs
        return c_full[:m, :n], err[:m, 0], col[0, :n]
    c_full, err = outs
    return c_full[:m, :n], err[:m, 0]
