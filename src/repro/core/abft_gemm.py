"""ABFT for low-precision GEMM — the paper's Algorithm 1, TPU-adapted.

Scheme (§IV):
  * encode only B (weights): ``rowSum[i] = (Σ_j B[i,j]) mod 127`` kept in int8,
  * run the one int8 GEMM with the checksum fused in (BLAS-3, §IV-A3),
  * verify per row: ``(Σ_j C[i,j]) mod 127 == (A @ rowSum)[i] mod 127`` — any
    mismatch marks row ``i`` corrupted; ``errCount`` is returned with C.

TPU adaptations (DESIGN.md §3):
  * the packed checksum is a 128-lane-aligned block (first lane = checksum,
    rest zero) instead of an ``n+1``-th column, keeping MXU tiles aligned;
  * row sums of C reduce ``C mod 127`` element-wise *before* the row sum so
    the verification is exact for any ``n`` (a raw int32 row sum can overflow
    for LLM-sized n; 2^32 is not ≡ 0 mod 127 so wraparound would otherwise
    produce false positives).

All functions are jit-safe and differentiable-free (integer domain).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

#: modulus of the paper (§IV-C): largest odd prime in the int8 value range.
MOD = 127

#: TPU lane width — the checksum block is padded to this many columns.
LANE = 128


class AbftGemmOut(NamedTuple):
    c: jax.Array           # int32 [m, n] — C_temp, checksum column excluded
    err_rows: jax.Array    # bool  [m]    — per-row violation of Eq. (3b)
    err_count: jax.Array   # int32 scalar — number of corrupted rows


def encode_weight_checksum(b_q: jax.Array, mod: int = MOD) -> jax.Array:
    """Alg. 1 lines 2-5: int8 mod-``mod`` row sums of B ([k, n] -> [k]).

    The sum is taken over int32 (exact: |entries| ≤ 128, n ≤ 2^24) and folded
    back into int8 via the modulus, so the checksum rides the int8 pipeline
    (§IV-A2).
    """
    rs = jnp.sum(b_q.astype(jnp.int32), axis=-1) % mod
    return rs.astype(jnp.int8)


def pack_encoded_b(b_q: jax.Array, checksum: Optional[jax.Array] = None,
                   mod: int = MOD, lanes: int = LANE) -> jax.Array:
    """Pack B' = [B | checksum-block] (§IV-A3, TPU-lane-aligned).

    Returns int8 [k, n + lanes]: the final ``lanes`` columns hold the checksum
    in lane 0 and zeros elsewhere, so every MXU tile stays 128-aligned.
    """
    if checksum is None:
        checksum = encode_weight_checksum(b_q, mod)
    k, _ = b_q.shape
    block = jnp.zeros((k, lanes), dtype=jnp.int8).at[:, 0].set(checksum)
    return jnp.concatenate([b_q, block], axis=1)


def _rowsum_mod(c: jax.Array, mod: int) -> jax.Array:
    """Exact ``(Σ_j c[..., j]) mod mod`` without int32 overflow for any n."""
    # (c mod m) ∈ [0, m); the row sum is ≤ 126 * n < 2^31 for n < 1.7e7.
    return jnp.sum(c % mod, axis=-1) % mod


def verify_rows(c: jax.Array, check_col: jax.Array,
                mod: int = MOD) -> Tuple[jax.Array, jax.Array]:
    """Eq. (3b) check: per-row mismatch mask + count.

    ``check_col`` is the int32 checksum product column ``A_I @ rowSum``.
    """
    expected = check_col % mod
    got = _rowsum_mod(c, mod)
    err_rows = got != expected
    return err_rows, jnp.sum(err_rows).astype(jnp.int32)


def abft_qgemm(a_q: jax.Array, b_q: jax.Array,
               checksum: Optional[jax.Array] = None,
               mod: int = MOD) -> AbftGemmOut:
    """Algorithm 1 with the checksum product fused into one GEMM (BLAS-3).

    a_q: uint8/int8 [m, k] activations, b_q: int8 [k, n] weights.
    When ``checksum`` (int8 [k]) is precomputed (the weight-amortized serving
    path, §IV-A1), encoding cost is zero per call.
    """
    b_packed = pack_encoded_b(b_q, checksum, mod)
    return abft_qgemm_packed(a_q, b_packed, mod)


def abft_qgemm_packed(a_q: jax.Array, b_packed: jax.Array,
                      mod: int = MOD, lanes: int = LANE) -> AbftGemmOut:
    """GEMM against a pre-packed B' and fused verification.

    This is the serving hot path: B' lives packed in memory (encode-once),
    each call is one int8 GEMM of width n+128 plus an O(mn) verify.
    """
    n = b_packed.shape[1] - lanes
    # int8 operands feed the dot directly (int32 accumulate) — converting
    # to int32 first materializes 4x-sized copies (§Perf hillclimb 3)
    c_full = jax.lax.dot_general(
        a_q, b_packed, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    c = c_full[:, :n]
    check_col = c_full[:, n]          # lane 0 of the checksum block
    err_rows, err_count = verify_rows(c, check_col, mod)
    return AbftGemmOut(c, err_rows, err_count)


def encode_activation_checksum(a_q: jax.Array) -> jax.Array:
    """Column-side encoding: exact int32 column sums of A ([m, k] -> [k]).

    ``encode_activation_checksum(a) @ B`` equals the exact column sums of
    ``C = A @ B`` — the second encoding axis single-error correction needs
    (the row side stays the mod-127 checksum of Alg. 1).
    """
    return jnp.sum(a_q.astype(jnp.int32), axis=0)


def correct_single_error(c: jax.Array, err_rows: jax.Array,
                         col_check: jax.Array
                         ) -> Tuple[jax.Array, jax.Array]:
    """Single-error correction (paper §IV intro): row/column checksum
    repair of one flagged cell.

    The mod-127 row check localizes the corrupted row i (``err_rows``);
    ``col_check`` — the EXACT expected int32 column sums of C, i.e.
    ``encode_activation_checksum(a) @ b`` (amortizable per batch) —
    localizes the column j AND yields the additive error magnitude, so
    ``C[i, j]`` is repaired in place.  Applies only when exactly one row
    and one column are flagged (the single-error model); anything else is
    left untouched for the recompute path.

    Returns ``(corrected_c, applied)`` where ``applied`` is a bool scalar.
    """
    delta = col_check.astype(jnp.int32) - jnp.sum(c, axis=0)
    j = jnp.argmax(jnp.abs(delta))
    i = jnp.argmax(err_rows)
    one_row = jnp.sum(err_rows.astype(jnp.int32)) == 1
    one_col = jnp.sum((delta != 0).astype(jnp.int32)) == 1
    applied = one_row & one_col
    fix = jnp.where(applied, delta[j], 0)
    return c.at[i, j].add(fix), applied


def encode_weight_colsum(b_q: jax.Array) -> jax.Array:
    """Weight-side column encoding: exact int32 column sums of B
    ([k, n] -> [n]), amortized at pack time like the row checksum.

    Together with the packed mod-127 row checksum this makes B itself a
    2D-checksummed block: a flipped weight is *localized* — the stale row
    checksum flags row k, the stale column sum flags column j and yields
    the exact additive delta — so the ``correct`` policy can repair the
    GEMM output without re-quantizing or re-running anything.
    """
    return jnp.sum(b_q.astype(jnp.int32), axis=-2)


def correct_weight_flip(c: jax.Array, a_q: jax.Array, b_packed: jax.Array,
                        colsum_ref: jax.Array, mod: int = MOD,
                        lanes: int = LANE) -> Tuple[jax.Array, jax.Array]:
    """Repair C after a single corrupted *weight* (not accumulator) cell.

    A flip in ``B[k0, j0]`` corrupts every row of column j0 of C — too
    many flagged rows for :func:`correct_single_error`'s single-cell
    model.  But the encodings of B localize it exactly:

    * recomputed mod-127 row sums vs the packed checksum lane flag k0
      (a single-bit int8 delta is ±2^b, never ≡ 0 mod 127);
    * recomputed column sums vs ``colsum_ref`` (the exact int32 sums of
      the clean B, stored at encode time) flag j0 *and* give the exact
      delta;
    * then ``C[:, j0] -= A[:, k0] * delta`` restores the clean product.

    Applies only when exactly one row and one column are flagged; a flip
    landing in the checksum lane or a multi-flip pattern leaves C
    untouched (``applied`` False) for the recompute fallback.
    Returns ``(corrected_c, applied)``.
    """
    n = b_packed.shape[1] - lanes
    b_q = b_packed[:, :n].astype(jnp.int32)
    row_ref = b_packed[:, n].astype(jnp.int32)
    row_bad = (jnp.sum(b_q, axis=-1) - row_ref) % mod != 0
    col_delta = jnp.sum(b_q, axis=0) - colsum_ref.astype(jnp.int32)
    col_bad = col_delta != 0
    k0 = jnp.argmax(row_bad)
    j0 = jnp.argmax(col_bad)
    applied = (jnp.sum(row_bad.astype(jnp.int32)) == 1) & \
        (jnp.sum(col_bad.astype(jnp.int32)) == 1)
    fix = jnp.where(applied, col_delta[j0], 0)
    return c.at[:, j0].add(-a_q.astype(jnp.int32)[:, k0] * fix), applied


# ---------------------------------------------------------------------------
# Detection-probability model (§IV-C) — used by tests and benchmarks to
# compare measured accuracy against the paper's analytical bounds.
# ---------------------------------------------------------------------------

def detect_prob_b_bitflip(m: int, mod: int = MOD) -> float:
    """§IV-C1 fault model 1: P[detect] = 1 - (3/256)^m."""
    assert mod == 127, "closed form derived for mod=127"
    return 1.0 - (3.0 / 256.0) ** m


def detect_prob_b_random(m: int, mod: int = MOD) -> float:
    """§IV-C1 fault model 2: P[detect] = 1 - (1018/32640)^m."""
    assert mod == 127
    return 1.0 - (1018.0 / 32640.0) ** m


def detect_prob_c_random(mod: int = MOD) -> float:
    """§IV-C2 fault model 2: P[detect] ≥ 1 - 1/mod."""
    return 1.0 - 1.0 / mod
