"""Public jit'd wrappers over the Pallas kernels.

Every wrapper takes one ``use_pallas``: True runs the Pallas kernel
(compiled with Mosaic on a TPU, in interpret mode anywhere else), False
the plain XLA reference (identical math), and None lets the platform
decide: Pallas on a TPU, the XLA reference elsewhere.

These wrappers are the operator surface the :mod:`repro.protect` adapters
dispatch to — layer code should not call them directly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import (AbftEbOut, EB_REL_BOUND, LANE,
                        abft_embedding_bag as _abft_eb_core,
                        encode_activation_checksum, verify_bags)
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _use_pallas(use_pallas: Optional[bool]) -> bool:
    """An explicit choice wins; None means Pallas exactly on a TPU."""
    return _on_tpu() if use_pallas is None else use_pallas


def abft_qgemm(a_q: jax.Array, b_packed: jax.Array, *,
               use_pallas: Optional[bool] = None, with_colcheck: bool = False,
               bm: int = 128, bn: int = 128, bk: int = 128):
    """ABFT int8 GEMM against a packed B'. -> (C int32, err_rows int32 [m]).

    ``with_colcheck=True`` additionally returns the **exact expected int32
    column sums of C** — ``encode_activation_checksum(A) @ B`` — the second
    encoding axis :func:`repro.core.correct_single_error` needs to localize
    and repair a single flagged cell.  The column product is a k×n matvec
    (one extra GEMM row's worth of MACs) and runs in int32 (an int8 column
    sum of A overflows int8, so it cannot ride the packed operand); it is
    therefore gated behind the flag and only paid by ``correct``-policy
    call sites.  On the Pallas path the matvec is fused into the kernel's
    per-tile pass, so the ``correct`` policy pays no second read of A/B'.
    """
    if _use_pallas(use_pallas):
        from repro.kernels.abft_qgemm import abft_qgemm_pallas
        return abft_qgemm_pallas(a_q, b_packed, bm=bm, bn=bn, bk=bk,
                                 interpret=not _on_tpu(),
                                 with_colcheck=with_colcheck)
    c, err_rows = _ref.abft_qgemm_ref(a_q, b_packed)
    if not with_colcheck:
        return c, err_rows
    n = b_packed.shape[1] - LANE
    col_a = encode_activation_checksum(a_q)                   # int32 [k]
    col_check = jax.lax.dot_general(
        col_a, b_packed[:, :n].astype(jnp.int32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return c, err_rows, col_check


def abft_embedding_bag(table_q, alphas, betas, indices, rowsums,
                       weights=None, *, rel_bound: float = EB_REL_BOUND,
                       use_pallas: Optional[bool] = None):
    """EB forward + Eq. (5) check. -> AbftEbOut(r, err_bags, err_count)."""
    if _use_pallas(use_pallas):
        from repro.kernels.abft_embeddingbag import abft_eb_pallas
        r, rsum = abft_eb_pallas(table_q, alphas, betas, indices, weights,
                                 interpret=not _on_tpu())
        # ONE Eq. (5) definition for both paths (repro.core.verify_bags):
        # the kernel's fused rsum feeds the shared check, so rel_bound
        # semantics cannot drift between XLA and Pallas
        err_bags = verify_bags(rsum, alphas, betas, indices, rowsums,
                               table_q.shape[-1], weights, rel_bound)
        return AbftEbOut(r, err_bags, jnp.sum(err_bags).astype(jnp.int32))
    return _abft_eb_core(table_q, alphas, betas, indices, rowsums,
                         weights, rel_bound)


def eb_slots(indices, d: int, *,
             use_pallas: Optional[bool] = None) -> int:
    """Slots (bag positions, −1 pads included) that
    :func:`abft_embedding_bag` runs on ``indices``: the Pallas kernel's
    grid, or every bag position of the XLA gather."""
    if _use_pallas(use_pallas):
        from repro.kernels.abft_embeddingbag import grid_slots
        return grid_slots(indices, d)
    return sum(i.size for i in jax.tree.leaves(indices))


def quantize_rows(x: jax.Array, *, use_pallas: Optional[bool] = None):
    """Per-row signed-int8 dynamic quantization. -> (q, alpha, beta)."""
    if _use_pallas(use_pallas):
        from repro.kernels.quantize_rows import quantize_rows_pallas
        return quantize_rows_pallas(x, interpret=not _on_tpu())
    return _ref.quantize_rows_ref(x)
