"""ABFT for low-precision EmbeddingBag — the paper's Algorithm 2 (§V).

EmbeddingBag with batch size n gathers rows ``I_b`` from a quantized table and
returns ``R_b = Σ_{i∈I_b} w_i (α_i · eb_i + β_i · e_d)`` per bag ``b``.

Detection invariant (Eq. 5, extended with optional per-index weights)::

    Σ_j R_b[j]  ==  Σ_{i∈I_b} w_i (α_i · C_T[i] + d · β_i)

with ``C_T[i] = Σ_j table[i, j]`` precomputed in *unscaled int32* (§V-B: this
minimizes float round-off in the checksum sum).  Since the EB output is
floating point, equality holds up to round-off; the check uses the paper's
loose relative bound (1e-5 by default, §V-D).

Batch layout: fixed-shape ``indices [bags, pool]`` padded with ``-1`` — the
JAX-native analogue of the offsets layout in torch.nn.EmbeddingBag.  Padded
slots contribute nothing to either side of Eq. 5.  Tables whose bags differ
in pool (DLRMv2's multi-hot features) come as a tuple with one ``[bags,
pool_t]`` array a table, of rows of one table that holds them all; the
results then gain a leading table axis.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: paper §V-D: loose relative bound to trade false positives for low-bit misses.
REL_BOUND = 1e-5

#: package-level alias (the name ``repro.core`` exports — "rel bound" alone
#: is ambiguous next to the float-GEMM bound).
EB_REL_BOUND = REL_BOUND


class AbftEbOut(NamedTuple):
    r: jax.Array           # f32 [bags, d]
    err_bags: jax.Array    # bool [bags]
    err_count: jax.Array   # int32 scalar


def table_rowsums(table_q: jax.Array) -> jax.Array:
    """Precompute ``C_T``: exact int32 row sums of the int8/int4 table.

    Amortized like the GEMM weight checksum — the table is frozen after
    training (§V-C), so this is computed once at model load.
    """
    return jnp.sum(table_q.astype(jnp.int32), axis=-1)


def _gather_terms(table_q, alphas, betas, indices, weights):
    """Shared gather of (rows, alpha, beta, weight, validity mask)."""
    valid = indices >= 0
    safe_idx = jnp.where(valid, indices, 0)
    rows = table_q[safe_idx].astype(jnp.float32)        # [bags, pool, d]
    a = alphas[safe_idx]                                 # [bags, pool]
    b = betas[safe_idx]
    w = jnp.ones_like(a) if weights is None else weights
    w = jnp.where(valid, w, 0.0)
    return rows, a, b, w


def embedding_bag(table_q: jax.Array, alphas: jax.Array, betas: jax.Array,
                  indices, weights=None) -> jax.Array:
    """The unprotected low-precision EB (§III-C): per-row dequant + bag sum."""
    if isinstance(indices, (tuple, list)):
        ws = (None,) * len(indices) if weights is None else weights
        return jnp.stack([embedding_bag(table_q, alphas, betas, i, w)
                          for i, w in zip(indices, ws)])
    rows, a, b, w = _gather_terms(table_q, alphas, betas, indices, weights)
    deq = a[..., None] * rows + b[..., None]             # [bags, pool, d]
    return jnp.sum(w[..., None] * deq, axis=1)           # [bags, d]


def verify_bags(rsum: jax.Array, alphas: jax.Array, betas: jax.Array,
                indices, rowsums: jax.Array, d: int, weights=None,
                rel_bound: float = REL_BOUND) -> jax.Array:
    """The Eq. (5) compare: per-bag error flags from the EB output row sums.

    ``rsum`` is ``Σ_j R_b[j]`` ([bags]), however the forward pass produced
    it (XLA reduction or the Pallas kernel's fused accumulator).  This is
    the ONE definition of the check — the ``rel_bound`` semantics (incl.
    ``threshold=adaptive`` controller moves) must not drift between
    execution paths, so both :func:`abft_embedding_bag` and the Pallas
    wrapper in :mod:`repro.kernels.ops` call here.

    |RSum - CSum| > bound  =>  soft error (Alg. 2 line 5).  The paper uses
    a bound relative to the result; float round-off however scales with
    the ACCUMULATED magnitude, so a cancellation-heavy bag (|Σx| ≪ Σ|x|)
    would false-positive.  We scale the bound by Σ|terms| instead —
    strictly fewer false positives at the paper's rel_bound (its measured
    9.5% FP rate is this very effect), same high-bit sensitivity.

    Ragged ``indices`` (a tuple, one table each) are checked in one pass
    over every slot: ``rsum`` [T, bags] -> flags [T, bags].
    """
    ragged = isinstance(indices, (tuple, list))
    idx = indices
    if ragged:
        # every table's slots in one pass (as the Pallas wrapper gathers
        # them, so that XLA gathers α and β once for both)
        idx = jnp.concatenate([i.reshape(-1) for i in indices])
        weights = None if weights is None else jnp.concatenate(
            [w.reshape(-1) for w in weights])
    valid = idx >= 0
    safe_idx = jnp.where(valid, idx, 0)
    a = alphas[safe_idx]
    b = betas[safe_idx]
    w = (jnp.ones_like(a) if weights is None else weights)
    w = jnp.where(valid, w, 0.0)
    ct = rowsums[safe_idx].astype(jnp.float32)           # [bags, pool]
    terms = w * (a * ct + d * b)
    mags = jnp.abs(w) * (jnp.abs(a) * jnp.abs(ct) + d * jnp.abs(b))
    if ragged:
        # the flat slots back into each table's bags
        at = np.cumsum([0] + [i.size for i in indices])
        per_bag = lambda x: jnp.stack([                     # noqa: E731
            x[at[t]:at[t + 1]].reshape(i.shape).sum(axis=-1)
            for t, i in enumerate(indices)])
    else:
        per_bag = lambda x: jnp.sum(x, axis=-1)             # noqa: E731
    csum, mag = per_bag(terms), per_bag(mags)            # [bags]
    tol = rel_bound * jnp.maximum(mag, 1.0)
    return jnp.abs(rsum - csum) > tol


def abft_embedding_bag(table_q: jax.Array, alphas: jax.Array,
                       betas: jax.Array, indices,
                       rowsums: jax.Array, weights=None,
                       rel_bound: float = REL_BOUND) -> AbftEbOut:
    """Algorithm 2: EB forward + Eq. (5) check per bag.

    ``rowsums`` is the precomputed ``C_T`` (int32 [rows]).
    """
    d = table_q.shape[-1]
    r = embedding_bag(table_q, alphas, betas, indices, weights)
    rsum = jnp.sum(r, axis=-1)                           # [bags]
    err_bags = verify_bags(rsum, alphas, betas, indices, rowsums, d,
                           weights, rel_bound)
    return AbftEbOut(r, err_bags, jnp.sum(err_bags).astype(jnp.int32))


def eb_overhead_model(m: int, d: int) -> float:
    """§V-C analytic overhead: (3m + d) extra ops over 3md ≈ 1/d + 1/(3m)."""
    return 1.0 / d + 1.0 / (3.0 * m)
