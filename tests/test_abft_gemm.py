"""ABFT for quantized GEMM (paper Alg. 1) — correctness + detection."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import given, settings, st

from repro.core import abft_gemm as ag
from repro.core.inject import flip_bit, random_bitflip, random_value


def _rand_ab(rng, m, k, n):
    a = rng.integers(0, 256, size=(m, k)).astype(np.uint8)
    b = rng.integers(-128, 128, size=(k, n)).astype(np.int8)
    return jnp.asarray(a), jnp.asarray(b)


# ------------------------- no-error behaviour -------------------------------

@pytest.mark.parametrize("m,k,n", [(1, 8, 8), (4, 64, 32), (13, 100, 77),
                                   (2, 800, 3200)])
def test_no_false_positives_and_correct_c(rng, m, k, n):
    a, b = _rand_ab(rng, m, k, n)
    out = ag.abft_qgemm(a, b)
    want = np.asarray(a, np.int64) @ np.asarray(b, np.int64)
    np.testing.assert_array_equal(np.asarray(out.c), want.astype(np.int32))
    assert int(out.err_count) == 0
    assert not bool(out.err_rows.any())


def test_packed_layout_lane_aligned(rng):
    _, b = _rand_ab(rng, 1, 16, 40)
    packed = ag.pack_encoded_b(b)
    assert packed.shape == (16, 40 + ag.LANE)
    # lane 0 of the block holds the mod-127 checksum, other lanes zero
    cs = np.asarray(ag.encode_weight_checksum(b))
    np.testing.assert_array_equal(np.asarray(packed[:, 40]), cs)
    assert not np.asarray(packed[:, 41:]).any()


def test_rowsum_mod_no_overflow():
    # A row of C that would overflow a raw int32 row sum must not trip the
    # check (the paper's scheme adapted for LLM-sized n; DESIGN.md §3).
    m, k, n = 1, 4096, 28672
    a = jnp.full((m, k), 255, jnp.uint8)
    b = jnp.full((k, n), 127, jnp.int8)
    out = ag.abft_qgemm(a, b)
    assert int(out.err_count) == 0


# ------------------------- detection behaviour ------------------------------

def test_detects_bitflip_in_c_always(rng):
    """§IV-C2 model 1: 127 divides no power of two => 100% detection."""
    a, b = _rand_ab(rng, 6, 32, 24)
    base = ag.abft_qgemm(a, b)
    packed = ag.pack_encoded_b(b)
    c_full = jnp.matmul(a.astype(jnp.int32), packed.astype(jnp.int32))
    for bit in range(31):
        corrupted = flip_bit(c_full, jnp.asarray(5), jnp.asarray(bit))
        err_rows, cnt = ag.verify_rows(corrupted[:, :24], corrupted[:, 24])
        assert int(cnt) >= 1, f"bit {bit} escaped"
    assert int(base.err_count) == 0


def test_detects_weight_corruption_with_high_probability(rng):
    """§IV-C1: bit flip in B detected with prob >= 1-(3/256)^m; with m=8
    that is ~1-1e-15, so 200/200 trials must detect."""
    a, b = _rand_ab(rng, 8, 64, 48)
    checksum = ag.encode_weight_checksum(b)  # encoded BEFORE corruption
    detected = 0
    for s in range(200):
        key = jax.random.PRNGKey(s)
        b_bad = random_bitflip(key, b)
        if (b_bad == b).all():
            detected += 1  # flip may hit the same value? impossible for xor
            continue
        out = ag.abft_qgemm(a, b_bad, checksum=checksum)
        detected += int(out.err_count) > 0
    assert detected == 200


def test_analytic_probability_helpers():
    assert ag.detect_prob_b_bitflip(1) == pytest.approx(1 - 3 / 256)
    assert ag.detect_prob_b_random(1) == pytest.approx(1 - 1018 / 32640)
    assert ag.detect_prob_c_random() == pytest.approx(1 - 1 / 127)
    assert ag.detect_prob_b_bitflip(20) >= 0.9883


# ------------------------- property-based tests -----------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(1, 12), st.integers(1, 48), st.integers(1, 48),
       st.integers(0, 2 ** 31 - 1))
def test_prop_no_error_never_flags(m, k, n, seed):
    """Invariant: an uncorrupted integer GEMM NEVER raises a flag (the paper
    measured 0/2800 false positives; in the integer domain it is exact)."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, 256, size=(m, k)), jnp.uint8)
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    out = ag.abft_qgemm(a, b)
    assert int(out.err_count) == 0


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 10), st.integers(2, 32), st.integers(2, 32),
       st.integers(0, 2 ** 31 - 1))
def test_prop_c_value_corruption_detected_unless_multiple_of_mod(m, k, n, seed):
    """A value replacement d in C is missed iff d ≡ 0 (mod 127) (§IV-C)."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, 256, size=(m, k)), jnp.uint8)
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    packed = ag.pack_encoded_b(b)
    c_full = jnp.matmul(a.astype(jnp.int32), packed.astype(jnp.int32))
    i = rng.integers(0, m)
    j = rng.integers(0, n)
    delta = int(rng.integers(1, 2 ** 20))
    corrupted = c_full.at[i, j].add(delta)
    _, cnt = ag.verify_rows(corrupted[:, :n], corrupted[:, n])
    if delta % 127 == 0:
        assert int(cnt) == 0   # the analytically-unavoidable escape
    else:
        assert int(cnt) == 1


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_prop_row_localization(seed):
    """A single corrupted element flags exactly its own row (enables
    row-granular recompute)."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, 256, size=(6, 16)), jnp.uint8)
    b = jnp.asarray(rng.integers(-128, 128, size=(16, 10)), jnp.int8)
    packed = ag.pack_encoded_b(b)
    c_full = jnp.matmul(a.astype(jnp.int32), packed.astype(jnp.int32))
    i = int(rng.integers(0, 6))
    corrupted = c_full.at[i, int(rng.integers(0, 10))].add(3)
    err_rows, _ = ag.verify_rows(corrupted[:, :10], corrupted[:, 10])
    assert bool(err_rows[i])
    assert int(err_rows.sum()) == 1


# ------------------------- weight-flip correction ---------------------------
# (B carries two encodings: the packed mod-127 row checksum plus exact
# int32 column sums.  A single flipped weight is localized to (k0, j0)
# with its exact delta and C repaired without recomputing anything.)

def _weight_flip_case(rng, m=4, k=16, n=12):
    a, b = _rand_ab(rng, m, k, n)
    packed = ag.pack_encoded_b(b)
    colsum = ag.encode_weight_colsum(b)
    want = (np.asarray(a, np.int64) @ np.asarray(b, np.int64)).astype(
        np.int32)
    return a, packed, colsum, want


def _c_of(a, packed, n):
    return jnp.asarray(np.asarray(a, np.int64)
                       @ np.asarray(packed)[:, :n].astype(np.int64),
                       jnp.int32)


def test_correct_weight_flip_repairs_payload_flip(rng):
    a, packed, colsum, want = _weight_flip_case(rng)
    bad = np.asarray(packed).copy()
    bad[5, 3] ^= np.int8(0x04)
    bad = jnp.asarray(bad)
    fixed, applied = ag.correct_weight_flip(_c_of(a, bad, 12), a, bad,
                                            colsum)
    assert bool(applied)
    np.testing.assert_array_equal(np.asarray(fixed), want)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(0, 7))
def test_correct_weight_flip_any_bit_property(seed, bit):
    """Every single-bit payload flip is repaired exactly: the int8 delta
    (+-2^b) is never 0 mod 127, so the row residue always flags k0."""
    rng = np.random.default_rng(seed)
    a, packed, colsum, want = _weight_flip_case(rng, m=3, k=12, n=8)
    bad = np.asarray(packed).copy()
    k0, j0 = int(rng.integers(12)), int(rng.integers(8))
    bad[k0, j0] ^= np.int8(-128) if bit == 7 else np.int8(1 << bit)
    bad = jnp.asarray(bad)
    fixed, applied = ag.correct_weight_flip(_c_of(a, bad, 8), a, bad,
                                            colsum)
    assert bool(applied)
    np.testing.assert_array_equal(np.asarray(fixed), want)


def test_correct_weight_flip_declines_outside_single_error_model(rng):
    a, packed, colsum, want = _weight_flip_case(rng, m=2, k=10, n=6)
    # clean B: nothing flagged, C untouched
    c = _c_of(a, packed, 6)
    fixed, applied = ag.correct_weight_flip(c, a, packed, colsum)
    assert not bool(applied)
    np.testing.assert_array_equal(np.asarray(fixed), np.asarray(c))
    # two flips in different rows/columns: not the single-error model
    two = np.asarray(packed).copy()
    two[1, 2] ^= np.int8(1)
    two[4, 5] ^= np.int8(2)
    two = jnp.asarray(two)
    c2 = _c_of(a, two, 6)
    fixed, applied = ag.correct_weight_flip(c2, a, two, colsum)
    assert not bool(applied)
    np.testing.assert_array_equal(np.asarray(fixed), np.asarray(c2))
    # a flip in the checksum lane flags a row but no column: declined,
    # and the (clean-payload) product stays untouched
    lane = np.asarray(packed).copy()
    lane[3, 6] ^= np.int8(1)
    lane = jnp.asarray(lane)
    fixed, applied = ag.correct_weight_flip(c, a, lane, colsum)
    assert not bool(applied)
    np.testing.assert_array_equal(np.asarray(fixed), want)
