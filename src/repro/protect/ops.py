"""The :class:`ProtectedOp` protocol and the registered adapters.

Every ABFT-protected operator family exposes one uniform surface:

* ``encode(params) -> encoded`` — the amortized, load-time encoding step
  (pack the weight checksum, precompute table row sums, quantize+checksum
  KV rows);
* ``__call__(encoded, *inputs, rule=...) -> (out, Check)`` — the protected
  hot-path call: run the op, verify, return the result plus a
  :class:`Check`;
* ``unprotected(encoded, *inputs) -> out`` — the baseline the overhead
  benchmarks (and disabled plan rules) run.

Adapters registered here (``qgemm``, ``float_gemm``, ``embedding_bag``,
``kv_cache``) dispatch through :mod:`repro.kernels.ops` where a Pallas
kernel exists, so scheme selection (``packed`` / ``unfused`` / ``pallas``)
is a plan concern, not a call-site concern.  Register a custom adapter with
:func:`register_op`; its name becomes a report key and a plan pattern.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Protocol, Tuple, \
    runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import (EB_REL_BOUND, LANE, QuantKV, abft_gemm_f32,
                        attend_quantized, correct_single_error,
                        correct_weight_flip, dequantize_kv, embedding_bag,
                        encode_activation_checksum, encode_weight_f32,
                        pack_encoded_b, quantize_kv_rows, table_rowsums,
                        update_kv_row, verify_rows)
from repro.core.policy import register_op_kind
from repro.kernels import ops as kops
from repro.paging.cache import attend_paged, paged_append
from repro.protect.plan import ResolvedRule

_DEFAULT_RULE = ResolvedRule()


class Check(NamedTuple):
    """What a protected call learned: residual error count, an optional
    per-row/bag error mask, and adapter-specific correction aux (e.g. the
    expected column sums the ``correct`` policy consumes)."""
    err_count: jax.Array
    err_mask: Optional[jax.Array] = None
    aux: Any = None


@runtime_checkable
class ProtectedOp(Protocol):
    """Structural protocol every adapter satisfies."""
    name: str
    schemes: Tuple[str, ...]
    supports_correct: bool

    def encode(self, params): ...                        # noqa: E704

    def __call__(self, encoded, *inputs, rule=None): ...  # noqa: E704

    def unprotected(self, encoded, *inputs): ...         # noqa: E704


# ---------------------------------------------------------------------------
# int8 GEMM (paper Algorithm 1)
# ---------------------------------------------------------------------------

class QGemmOp:
    """Quantized GEMM: encoded = packed B' (int8 [k, n+LANE]), input = A_q.

    Schemes: ``packed`` (fused checksum column, Pallas on TPU / XLA ref on
    CPU), ``pallas`` (force the Pallas kernel, interpret-mode off-TPU),
    ``unfused`` (the BLAS-2 baseline the paper argues against §IV-A3).

    ``encoded`` may also be ``(packed, colsum_ref)`` where ``colsum_ref``
    is the exact int32 column sums of the clean B block (amortized at
    pack time, like the row checksum).  With it, the ``correct`` policy
    additionally repairs single *weight* flips — a corrupted ``B[k, j]``
    poisons a whole output column, which the single-cell accumulator
    repair cannot handle, but the two stale B encodings localize (k, j)
    and the exact delta (:func:`repro.core.correct_weight_flip`).
    """
    name = "qgemm"
    schemes = ("packed", "pallas", "unfused")
    supports_correct = True
    lane = LANE

    def encode(self, w_q: jax.Array) -> jax.Array:
        return pack_encoded_b(w_q)

    @staticmethod
    def _unpack(encoded):
        if isinstance(encoded, tuple):
            return encoded
        return encoded, None

    def out_dim(self, encoded) -> int:
        packed, _ = self._unpack(encoded)
        return packed.shape[-1] - LANE

    def dequant_colsum(self, w_q: jax.Array) -> jax.Array:
        """The Eq. 1 rank-1 requantization constant: f32 column sums of
        the int8 weight block ([..., k, n] -> [..., n]).  One definition —
        a colsum out of sync with the weights is silent output corruption,
        not a detection miss, so every producer (init, quantization,
        re-encoding) must share it."""
        return jnp.sum(w_q.astype(jnp.int32), axis=-2).astype(jnp.float32)

    def _aux(self, col_check, a_q, packed, colsum_ref):
        if col_check is not None and colsum_ref is not None:
            return {"col_check": col_check, "a_q": a_q, "packed": packed,
                    "colsum_ref": colsum_ref}
        return col_check

    def __call__(self, encoded, a_q, *, rule: ResolvedRule = _DEFAULT_RULE):
        packed, colsum_ref = self._unpack(encoded)
        scheme = rule.scheme or "packed"
        want_col = rule.policy == "correct"
        n = self.out_dim(packed)
        if scheme == "unfused":
            b_q = packed[:, :n]
            checksum = packed[:, n]                        # lane 0 of block
            c = jax.lax.dot_general(a_q, b_q, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.int32)
            check_col = jax.lax.dot_general(
                a_q, checksum, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            err_rows, err = verify_rows(c, check_col)
            col_check = None
            if want_col:
                col_check = jax.lax.dot_general(
                    encode_activation_checksum(a_q),
                    b_q.astype(jnp.int32), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
            return c, Check(err, err_rows,
                            self._aux(col_check, a_q, packed, colsum_ref))
        if scheme not in ("packed", "pallas"):
            raise ValueError(f"unknown qgemm scheme {scheme!r}; "
                             f"have {self.schemes}")
        use_pallas = True if scheme == "pallas" else None
        out = kops.abft_qgemm(a_q, packed, use_pallas=use_pallas,
                              with_colcheck=want_col)
        if want_col:
            c, err_rows, col_check = out
        else:
            (c, err_rows), col_check = out, None
        err_mask = err_rows.astype(bool)
        return c, Check(jnp.sum(err_rows).astype(jnp.int32), err_mask,
                        self._aux(col_check, a_q, packed, colsum_ref))

    def unprotected(self, encoded, a_q):
        packed, _ = self._unpack(encoded)
        n = self.out_dim(packed)
        return jax.lax.dot_general(a_q, packed[:, :n],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)

    def correct(self, out, check: Check):
        """Single-error repair; returns (fixed, residual_err, applied).

        Tries the single-cell accumulator repair first, then (when the
        encoded side carried a column-sum reference) the weight-flip
        repair.  The two cannot mis-fire together: a weight flip leaves
        the accumulator column deltas self-consistent (zero), and a
        clean B leaves the weight encodings self-consistent.
        """
        aux = check.aux
        if isinstance(aux, dict):
            fixed, cell = correct_single_error(out, check.err_mask,
                                               aux["col_check"])
            fixed, wflip = correct_weight_flip(fixed, aux["a_q"],
                                               aux["packed"],
                                               aux["colsum_ref"])
            applied = cell | wflip
        else:
            fixed, applied = correct_single_error(out, check.err_mask, aux)
        residual = jnp.where(applied, 0, check.err_count).astype(jnp.int32)
        return fixed, residual, applied.astype(jnp.int32)


# ---------------------------------------------------------------------------
# float GEMM (beyond-paper: training-time bf16/f32 matmuls)
# ---------------------------------------------------------------------------

class FloatGemmOp:
    """Float ABFT GEMM: encoded = (W, f32 row sums | None), input = A."""
    name = "float_gemm"
    schemes = ("default",)
    supports_correct = False

    def encode(self, w: jax.Array):
        return (w, encode_weight_f32(w))

    def __call__(self, encoded, a, *, rule: ResolvedRule = _DEFAULT_RULE):
        w, checksum = encoded if isinstance(encoded, tuple) else (encoded,
                                                                  None)
        rel = 1e-3 if rule.rel_bound is None else rule.rel_bound
        out = abft_gemm_f32(a, w, checksum=checksum, rel_bound=rel)
        return out.c, Check(out.err_count, out.err_rows)

    def unprotected(self, encoded, a):
        w = encoded[0] if isinstance(encoded, tuple) else encoded
        return jnp.dot(a.astype(jnp.float32), w.astype(jnp.float32),
                       preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# EmbeddingBag (paper Algorithm 2)
# ---------------------------------------------------------------------------

class EmbeddingBagOp:
    """Quantized EB: encoded = (table_q, alphas, betas, rowsums);
    inputs = (indices [bags, pool] (−1 padded), optional weights).

    ``indices`` may be a tuple with one [bags, pool_t] array a table, of
    rows of a table that holds every table's rows: one call then looks
    up every table and counts one check a table.  The report's
    ``eb_slots`` counts the slots the lookup ran (the kernel's grid)."""
    name = "embedding_bag"
    schemes = ("xla", "pallas")
    supports_correct = False
    default_rel_bound = EB_REL_BOUND

    def encode(self, params):
        """(table, alphas, betas) -> the 4-tuple with fresh row sums."""
        table_q, alphas, betas = params
        return (table_q, alphas, betas, table_rowsums(table_q))

    def __call__(self, encoded, indices, weights=None, *,
                 rule: ResolvedRule = _DEFAULT_RULE):
        table_q, alphas, betas, rowsums = encoded
        rel = self.default_rel_bound if rule.rel_bound is None \
            else rule.rel_bound
        if rule.scheme is None:
            use_pallas = None                      # auto: Pallas on TPU
        elif rule.scheme == "pallas":
            use_pallas = True
        elif rule.scheme == "xla":
            use_pallas = False
        else:
            raise ValueError(f"unknown embedding_bag scheme "
                             f"{rule.scheme!r}; have {self.schemes}")
        out = kops.abft_embedding_bag(table_q, alphas, betas, indices,
                                      rowsums, weights, rel_bound=rel,
                                      use_pallas=use_pallas)
        aux = {"eb_slots": kops.eb_slots(indices, table_q.shape[-1],
                                         use_pallas=use_pallas)}
        if isinstance(indices, (tuple, list)):
            aux["n_checks"] = len(indices)
        return out.r, Check(out.err_count, out.err_bags, aux)

    def unprotected(self, encoded, indices, weights=None):
        table_q, alphas, betas, _ = encoded
        return embedding_bag(table_q, alphas, betas, indices, weights)


# ---------------------------------------------------------------------------
# Quantized KV cache (beyond-paper)
# ---------------------------------------------------------------------------

class KvCacheOp:
    """Checksummed int8 KV cache: encoded = (kv_k, kv_v) QuantKV pair;
    inputs = (q_heads [B, H, dh], pos [B]); static n_heads/n_kv plus the
    window/prefix masking of ``layers.attention.attention_decode``."""
    name = "kv_cache"
    schemes = ("default",)
    supports_correct = False

    def encode(self, kv):
        """Float K/V rows ([..., S, dh]) -> QuantKV (quantize + checksum).

        Accepts a single array or a (k, v) tuple."""
        if isinstance(kv, tuple):
            return tuple(quantize_kv_rows(x) for x in kv)
        return quantize_kv_rows(kv)

    def update(self, kv: QuantKV, batch_idx, pos, new_row) -> QuantKV:
        return update_kv_row(kv, batch_idx, pos, new_row)

    def __call__(self, encoded, q_heads, pos, *,
                 rule: ResolvedRule = _DEFAULT_RULE, n_heads: int,
                 n_kv: int, window=None, prefix_global: int = 0):
        kv_k, kv_v = encoded
        out, errs = attend_quantized(q_heads, kv_k, kv_v, pos,
                                     n_heads=n_heads, n_kv=n_kv,
                                     verify=True, window=window,
                                     prefix_global=prefix_global)
        return out, Check(errs)

    def unprotected(self, encoded, q_heads, pos, *, n_heads: int,
                    n_kv: int, window=None, prefix_global: int = 0):
        kv_k, kv_v = encoded
        out, _ = attend_quantized(q_heads, kv_k, kv_v, pos,
                                  n_heads=n_heads, n_kv=n_kv,
                                  verify=False, window=window,
                                  prefix_global=prefix_global)
        return out

    def dequantize(self, kv: QuantKV, dtype=jnp.bfloat16):
        return dequantize_kv(kv, dtype)


# ---------------------------------------------------------------------------
# Paged quantized KV cache (repro.paging)
# ---------------------------------------------------------------------------

class KvCachePagedOp:
    """Page-table int8 KV cache with per-page folded checksums.

    encoded = (pk, pv) :class:`repro.paging.PagedKV` pair (per-layer
    layout); inputs = (q_heads [B, H, dh], pos [B]).  Verify-on-touch:
    the check covers exactly the pages the attention mask reads, one
    int32 compare per (page, kv head), and the touched-page count rides
    the report's ``checks`` counter so telemetry can price verification
    per decode token.  Page repair (evict/rebuild/abort-owner) is a
    host-side allocator action — the serving engine interprets the plan
    policy; in-jit the op only counts, so call sites pass a log-policy
    rule.
    """
    name = "kv_cache_paged"
    schemes = ("default",)
    supports_correct = False

    def encode(self, kv):
        """Pool encoding lives in :mod:`repro.paging.cache`
        (pack_prompt_pages / paged_append); pass pools through."""
        return kv

    def append(self, pk, pos, new_rows):
        return paged_append(pk, pos, new_rows)

    def __call__(self, encoded, q_heads, pos, *,
                 rule: ResolvedRule = _DEFAULT_RULE, n_heads: int,
                 n_kv: int, window=None, prefix_global: int = 0):
        pk, pv = encoded
        out, errs, pages = attend_paged(q_heads, pk, pv, pos,
                                        n_heads=n_heads, n_kv=n_kv,
                                        verify=True, window=window,
                                        prefix_global=prefix_global)
        return out, Check(errs, aux={"n_checks": pages})

    def unprotected(self, encoded, q_heads, pos, *, n_heads: int,
                    n_kv: int, window=None, prefix_global: int = 0):
        pk, pv = encoded
        out, _, _ = attend_paged(q_heads, pk, pv, pos, n_heads=n_heads,
                                 n_kv=n_kv, verify=False, window=window,
                                 prefix_global=prefix_global)
        return out


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

OPS: Dict[str, ProtectedOp] = {}


def register_op(op: ProtectedOp) -> ProtectedOp:
    """Register an adapter; its name becomes a FaultReport key and a plan
    pattern.  Call at import time (report pytree structure is static)."""
    OPS[op.name] = op
    register_op_kind(op.name)
    return op


def get_op(name: str) -> ProtectedOp:
    if name not in OPS:
        raise KeyError(f"unknown protected op {name!r}; "
                       f"registered: {sorted(OPS)}")
    return OPS[name]


QGEMM = register_op(QGemmOp())
FLOAT_GEMM = register_op(FloatGemmOp())
EMBEDDING_BAG = register_op(EmbeddingBagOp())
KV_CACHE = register_op(KvCacheOp())
KV_CACHE_PAGED = register_op(KvCachePagedOp())

__all__ = ["Check", "ProtectedOp", "OPS", "register_op", "get_op",
           "QGemmOp", "FloatGemmOp", "EmbeddingBagOp", "KvCacheOp",
           "KvCachePagedOp", "QGEMM", "FLOAT_GEMM", "EMBEDDING_BAG",
           "KV_CACHE", "KV_CACHE_PAGED", "QuantKV", "LANE"]
