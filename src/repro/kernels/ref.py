"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import MOD, abft_qgemm_packed, embedding_bag


def int8_dot(a_q: jax.Array, b_q: jax.Array) -> jax.Array:
    """int8 x int8 -> int32 dot WITHOUT materializing int32 operands.

    ``a.astype(int32) @ b.astype(int32)`` writes 4x-sized converted copies
    of both operands to HBM on every call (measured: +2.8 TB/token on the
    123B decode cell — EXPERIMENTS §Perf hillclimb 3).  The MXU consumes
    int8 natively; expressing the dot on int8 operands with an int32
    accumulator is both the TPU-faithful form and the XLA fix.
    """
    return jax.lax.dot_general(a_q, b_q, (((a_q.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def abft_qgemm_ref(a_q: jax.Array, b_packed: jax.Array, mod: int = MOD):
    """Oracle for kernels.abft_qgemm: (C int32 [m,n], err_rows int32 [m])."""
    out = abft_qgemm_packed(a_q, b_packed, mod)
    return out.c, out.err_rows.astype(jnp.int32)


def abft_eb_ref(table_q, alphas, betas, indices, weights=None):
    """Oracle for kernels.abft_embeddingbag: (R [bags,d], rsum [bags])."""
    r = embedding_bag(table_q, alphas, betas, indices, weights)
    return r, jnp.sum(r, axis=-1)


def quantize_rows_ref(x: jax.Array):
    """Oracle for kernels.quantize_rows (signed int8 per-row affine)."""
    x = x.astype(jnp.float32)
    xmin = jnp.min(x, axis=1)
    xmax = jnp.max(x, axis=1)
    span = jnp.maximum(xmax - xmin, 1e-12)
    alpha = span / 255.0
    beta = xmin + 128.0 * alpha
    q = jnp.clip(jnp.round((x - beta[:, None]) / alpha[:, None]), -128, 127)
    return q.astype(jnp.int8), alpha, beta
