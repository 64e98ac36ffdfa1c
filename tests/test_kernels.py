"""Pallas kernels vs. pure-jnp oracles — shape/dtype sweeps (interpret=True)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abft_gemm import encode_weight_checksum, pack_encoded_b
from repro.core.inject import flip_bit
from repro.kernels import ref as kref
from repro.kernels.abft_embeddingbag import RING, abft_eb_pallas
from repro.kernels.abft_qgemm import abft_qgemm_pallas
from repro.kernels.quantize_rows import quantize_rows_pallas
from repro.kernels import ops


# ---------------------------- abft_qgemm -----------------------------------

QGEMM_SHAPES = [
    # (m, k, n) — DLRM-ish skinny, tile-aligned, ragged, LLM-wide
    (1, 64, 64),
    (8, 128, 128),
    (16, 256, 512),
    (5, 100, 77),
    (130, 70, 300),
    (2, 800, 3200),
]


@pytest.mark.parametrize("m,k,n", QGEMM_SHAPES)
def test_qgemm_kernel_matches_ref(rng, m, k, n):
    a = jnp.asarray(rng.integers(-128, 128, size=(m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    bp = pack_encoded_b(b)
    c_ref, err_ref = kref.abft_qgemm_ref(a, bp)
    c, err = abft_qgemm_pallas(a, bp, interpret=True)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))
    np.testing.assert_array_equal(np.asarray(err), np.asarray(err_ref))
    assert int(err.sum()) == 0


@pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128), (64, 256, 128),
                                      (128, 64, 64), (256, 128, 256)])
def test_qgemm_kernel_block_shapes(rng, bm, bn, bk):
    m, k, n = 48, 160, 200
    a = jnp.asarray(rng.integers(-128, 128, size=(m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    bp = pack_encoded_b(b)
    c_ref, _ = kref.abft_qgemm_ref(a, bp)
    c, err = abft_qgemm_pallas(a, bp, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))
    assert int(err.sum()) == 0


def test_qgemm_kernel_detects_corrupted_weights(rng):
    m, k, n = 8, 64, 96
    a = jnp.asarray(rng.integers(-128, 128, size=(m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    checksum = encode_weight_checksum(b)          # clean checksum
    detected = 0
    for s in range(20):
        b_bad = flip_bit(b, jnp.asarray(s * 41 % (k * n)),
                         jnp.asarray(s % 8))
        bp = pack_encoded_b(b_bad, checksum)      # checksum NOT recomputed
        _, err = abft_qgemm_pallas(a, bp, interpret=True)
        detected += int(err.sum()) > 0
    assert detected == 20  # P[miss] = (3/256)^8 ~ 1e-16 per trial


def test_qgemm_ops_dispatch_xla_path(rng):
    a = jnp.asarray(rng.integers(-128, 128, size=(4, 32)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, size=(32, 16)), jnp.int8)
    bp = pack_encoded_b(b)
    c1, e1 = ops.abft_qgemm(a, bp, use_pallas=False)
    c2, e2 = ops.abft_qgemm(a, bp, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


# --------------------- abft_qgemm: uint8 zero-point path --------------------

def test_qgemm_kernel_uint8_matches_ref(rng):
    # regression: the old wrapper did a bare astype(int8), silently
    # reinterpreting activations >= 128 as negative: uint8 activations
    # with half their values at or above 128.
    m, k, n = 20, 256, 512
    a = jnp.asarray(rng.integers(0, 256, size=(m, k)), jnp.uint8)
    assert int(jnp.max(a)) >= 128            # the wraparound-triggering half
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    bp = pack_encoded_b(b)
    c_ref, err_ref = kref.abft_qgemm_ref(a, bp)
    c, err = abft_qgemm_pallas(a, bp, interpret=True)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))
    np.testing.assert_array_equal(np.asarray(err), np.asarray(err_ref))
    assert int(err.sum()) == 0


def test_qgemm_kernel_uint8_detects_corrupted_weights(rng):
    m, k, n = 8, 64, 96
    a = jnp.asarray(rng.integers(0, 256, size=(m, k)), jnp.uint8)
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    checksum = encode_weight_checksum(b)
    detected = 0
    for s in range(20):
        b_bad = flip_bit(b, jnp.asarray(s * 41 % (k * n)),
                         jnp.asarray(s % 8))
        bp = pack_encoded_b(b_bad, checksum)
        c_ref, err_ref = kref.abft_qgemm_ref(a, bp)
        c, err = abft_qgemm_pallas(a, bp, interpret=True)
        # flags bit-identical to the unsigned reference, not just "some flag"
        np.testing.assert_array_equal(np.asarray(err), np.asarray(err_ref))
        detected += int(err.sum()) > 0
    assert detected == 20


def test_qgemm_kernel_rejects_bad_dtypes(rng):
    a_f = jnp.ones((4, 32), jnp.float32)
    b = jnp.asarray(rng.integers(-128, 128, size=(32, 16)), jnp.int8)
    bp = pack_encoded_b(b)
    with pytest.raises(TypeError, match="int8 or uint8"):
        abft_qgemm_pallas(a_f, bp, interpret=True)
    a = jnp.asarray(rng.integers(-128, 128, size=(4, 32)), jnp.int8)
    with pytest.raises(TypeError, match="int8"):
        abft_qgemm_pallas(a, bp.astype(jnp.int32), interpret=True)


# ----------------- abft_qgemm: bn < LANE multi-tile checksum ----------------

@pytest.mark.parametrize("bn", [32, 64])
@pytest.mark.parametrize("m,k,n", [(8, 64, 96), (5, 100, 77)])
def test_qgemm_kernel_small_bn_clean(rng, bn, m, k, n):
    # the checksum block spans LANE/bn > 1 tiles: lane 0 of the first
    # carries the check, the trailing tiles must stay inert
    a = jnp.asarray(rng.integers(-128, 128, size=(m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    bp = pack_encoded_b(b)
    c_ref, _ = kref.abft_qgemm_ref(a, bp)
    c, err = abft_qgemm_pallas(a, bp, bm=32, bn=bn, bk=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))
    assert int(err.sum()) == 0


@pytest.mark.parametrize("bn", [32, 64])
def test_qgemm_kernel_small_bn_detects(rng, bn):
    m, k, n = 8, 64, 96
    a = jnp.asarray(rng.integers(-128, 128, size=(m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    checksum = encode_weight_checksum(b)
    detected = 0
    for s in range(20):
        b_bad = flip_bit(b, jnp.asarray(s * 41 % (k * n)),
                         jnp.asarray(s % 8))
        bp = pack_encoded_b(b_bad, checksum)
        _, err = abft_qgemm_pallas(a, bp, bm=32, bn=bn, bk=64,
                                   interpret=True)
        detected += int(err.sum()) > 0
    assert detected == 20


# --------------------- abft_qgemm: fused Eq.-1 colcheck ---------------------

@pytest.mark.parametrize("dtype", ["int8", "uint8"])
@pytest.mark.parametrize("bn", [64, 128])
def test_qgemm_kernel_fused_colcheck(rng, dtype, bn):
    from repro.core import encode_activation_checksum
    m, k, n = 12, 100, 200
    lo, hi = (-128, 128) if dtype == "int8" else (0, 256)
    a = jnp.asarray(rng.integers(lo, hi, size=(m, k)), getattr(jnp, dtype))
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    bp = pack_encoded_b(b)
    c, err, col = abft_qgemm_pallas(a, bp, bn=bn, interpret=True,
                                    with_colcheck=True)
    c_ref, err_ref = kref.abft_qgemm_ref(a, bp)
    col_ref = jax.lax.dot_general(
        encode_activation_checksum(a), bp[:, :n].astype(jnp.int32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))
    np.testing.assert_array_equal(np.asarray(err), np.asarray(err_ref))
    np.testing.assert_array_equal(np.asarray(col), np.asarray(col_ref))


def test_qgemm_ops_colcheck_paths_agree(rng):
    # ops-level: the fused kernel's colcheck must equal the XLA wrapper
    # matvec, so the `correct` policy sees the same Eq.-1 reference on
    # both schemes
    a = jnp.asarray(rng.integers(0, 256, size=(6, 64)), jnp.uint8)
    b = jnp.asarray(rng.integers(-128, 128, size=(64, 48)), jnp.int8)
    bp = pack_encoded_b(b)
    c_x, e_x, col_x = ops.abft_qgemm(a, bp, use_pallas=False,
                                     with_colcheck=True)
    c_p, e_p, col_p = ops.abft_qgemm(a, bp, use_pallas=True,
                                     with_colcheck=True)
    np.testing.assert_array_equal(np.asarray(c_x), np.asarray(c_p))
    np.testing.assert_array_equal(np.asarray(e_x), np.asarray(e_p))
    np.testing.assert_array_equal(np.asarray(col_x), np.asarray(col_p))


def test_qgemm_correct_policy_pallas_scheme(rng):
    from repro.protect.ops import QGEMM
    from repro.protect.plan import ResolvedRule
    a = jnp.asarray(rng.integers(0, 256, size=(6, 64)), jnp.uint8)
    b = jnp.asarray(rng.integers(-128, 128, size=(64, 48)), jnp.int8)
    packed = QGEMM.encode(b)
    c_p, chk_p = QGEMM(packed, a,
                       rule=ResolvedRule(scheme="pallas", policy="correct"))
    c_x, chk_x = QGEMM(packed, a,
                       rule=ResolvedRule(scheme="xla", policy="correct"))
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_x))
    np.testing.assert_array_equal(np.asarray(chk_p.aux),
                                  np.asarray(chk_x.aux))


# ------------------- ops dispatch: explicit scheme wins ---------------------

def test_ops_explicit_false_beats_interpret(rng, monkeypatch):
    # use_pallas=False must take the XLA path whatever the platform.
    # Poison the kernel entry points; the XLA path must never touch them.
    import repro.kernels.abft_embeddingbag as eb_mod
    import repro.kernels.abft_qgemm as qg_mod
    import repro.kernels.quantize_rows as qr_mod

    def _boom(*a, **kw):
        raise AssertionError("explicit use_pallas=False reached Pallas")

    monkeypatch.setattr(qg_mod, "abft_qgemm_pallas", _boom)
    monkeypatch.setattr(eb_mod, "abft_eb_pallas", _boom)
    monkeypatch.setattr(qr_mod, "quantize_rows_pallas", _boom)

    a = jnp.asarray(rng.integers(-128, 128, size=(4, 32)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, size=(32, 16)), jnp.int8)
    bp = pack_encoded_b(b)
    c, err = ops.abft_qgemm(a, bp, use_pallas=False)
    assert int(err.sum()) == 0

    from repro.core.abft_embedding import table_rowsums
    t = jnp.asarray(rng.integers(-128, 128, size=(64, 32)), jnp.int8)
    al = jnp.asarray(rng.uniform(0.01, 0.1, size=64), jnp.float32)
    be = jnp.asarray(rng.uniform(-0.1, 0.1, size=64), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 64, size=(2, 8)), jnp.int32)
    out = ops.abft_embedding_bag(t, al, be, idx, table_rowsums(t),
                                 use_pallas=False)
    assert int(out.err_count) == 0

    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    q, _, _ = ops.quantize_rows(x, use_pallas=False)
    assert q.dtype == jnp.int8


# ----------- fused vs reference: deterministic detection parity ------------

def test_qgemm_fused_reference_err_parity(rng):
    # the SAME stale-checksum flips through the fused Pallas path and the
    # XLA reference scheme: Eq. (3b) is one criterion, so the per-row
    # flags must agree flip for flip (the --grid pallas campaign gate is
    # the statistical version of this at scale)
    from repro.protect.ops import QGEMM
    from repro.protect.plan import ResolvedRule
    m, k, n = 8, 64, 96
    a = jnp.asarray(rng.integers(0, 256, size=(m, k)), jnp.uint8)
    b = jnp.asarray(rng.integers(-128, 128, size=(k, n)), jnp.int8)
    checksum = encode_weight_checksum(b)
    reference = ResolvedRule(scheme="xla")
    for s in range(10):
        b_bad = flip_bit(b, jnp.asarray(s * 41 % (k * n)),
                         jnp.asarray(s % 8))
        bp = pack_encoded_b(b_bad, checksum)
        _, err_fused = abft_qgemm_pallas(a, bp, interpret=True)
        _, chk = QGEMM(bp, a, rule=reference)
        np.testing.assert_array_equal(np.asarray(err_fused).astype(bool),
                                      np.asarray(chk.err_mask))


# ---------------------------- abft_embeddingbag ----------------------------

EB_SHAPES = [
    # (rows, d, bags, pool)
    (256, 32, 4, 10),
    (1024, 64, 10, 100),
    (512, 128, 2, 7),
    (100, 16, 1, 1),
    # pool 1, many bags: several bag blocks, the last one partly full
    (4000, 32, 2500, 1),
    # d = 64, 21 bags in blocks of 8 (the last holds 5), pool > RING
    (1000, 64, 21, 100),
    # a pool more than twice the DMA ring, rows % 8 != 0
    (203, 128, 3, 2 * RING + 3),
    # a table smaller than one row group: every row from the tail group
    (5, 32, 6, 5),
]


def _eb_inputs(rng, lead, rows, d):
    t = jnp.asarray(rng.integers(-128, 128, size=lead + (rows, d)), jnp.int8)
    al = jnp.asarray(rng.uniform(0.001, 0.1, size=lead + (rows,)),
                     jnp.float32)
    be = jnp.asarray(rng.uniform(-0.5, 0.5, size=lead + (rows,)),
                     jnp.float32)
    return t, al, be


@pytest.mark.parametrize("rows,d,bags,pool", EB_SHAPES)
def test_eb_kernel_matches_ref(rng, rows, d, bags, pool):
    t, al, be = _eb_inputs(rng, (), rows, d)
    idx = rng.integers(0, rows, size=(bags, pool))
    idx[0, 0] = rows - 1                      # the table's last row is read
    idx = jnp.asarray(idx, jnp.int32)
    r_ref, rsum_ref = kref.abft_eb_ref(t, al, be, idx)
    r, rsum = abft_eb_pallas(t, al, be, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_ref), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(rsum), np.asarray(rsum_ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("layout,rows,d,bags,pool", [
    ("tables", 200, 64, 5, 9),
    ("shared", 200, 64, 5, 9),
    # pool 1: the shared layout stacks 3 x 700 bags into several blocks
    ("tables", 2003, 128, 700, 1),
    ("shared", 2003, 128, 700, 1),
], ids=["tables", "shared", "tables-pool1", "shared-pool1"])
def test_eb_kernel_vmap_matches_ref(rng, layout, rows, d, bags, pool):
    """Both branches of the kernel's vmap rule: stacked tables, each with
    its own bags (the DLRM table vmap), and one table shared by every
    mapped bag set (the campaign's clean trials).  Indices avoid multiples
    of ROW_GROUP so the row is picked from inside its fetched group; each
    bag set also reads the table's last row."""
    from repro.kernels.abft_embeddingbag import ROW_GROUP
    n = 3
    stacked = layout == "tables"
    t, al, be = _eb_inputs(rng, (n,) if stacked else (), rows, d)
    idx = rng.integers(0, rows // ROW_GROUP, size=(n, bags, pool)) \
        * ROW_GROUP + rng.integers(1, ROW_GROUP, size=(n, bags, pool))
    idx[:, -1, -1] = rows - 1
    idx = jnp.asarray(idx, jnp.int32)
    axes = (0, 0, 0, 0) if stacked else (None, None, None, 0)

    def vmapped(fn):
        return jax.vmap(fn, in_axes=axes)(t, al, be, idx)

    r_ref, rsum_ref = vmapped(kref.abft_eb_ref)
    r, rsum = vmapped(functools.partial(abft_eb_pallas, interpret=True))
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_ref), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(rsum), np.asarray(rsum_ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("rows,d,bags,pool", [
    (64, 32, 2, 4),
    # pool 1 with many bags, about a third of them empty (-1)
    (1000, 128, 3000, 1),
    # bags not a multiple of the bag block, rows % 8 != 0, pool > RING
    (301, 64, 19, 45),
])
def test_eb_kernel_padding_and_weights(rng, rows, d, bags, pool):
    """−1 slots contribute nothing whatever their weight; weights scale
    each row (in the generated cases, also the table's last row)."""
    t, al, be = _eb_inputs(rng, (), rows, d)
    if bags == 2:            # the hand-written case
        idx = np.asarray([[3, 9, -1, -1], [5, -1, -1, -1]])
        w = np.asarray([[1.0, 2.0, 9.9, 9.9], [0.5, 9.9, 9.9, 9.9]])
    else:
        idx = rng.integers(0, rows, size=(bags, pool))
        idx[rng.uniform(size=(bags, pool)) < 0.35] = -1
        idx[-1, 0] = rows - 1
        w = rng.uniform(-2.0, 2.0, size=(bags, pool))
    idx = jnp.asarray(idx, jnp.int32)
    w = jnp.asarray(w, jnp.float32)
    r_ref, _ = kref.abft_eb_ref(t, al, be, idx, w)
    r, _ = abft_eb_pallas(t, al, be, idx, w, interpret=True)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("rows,pools,bags,d", [
    # a 3-row table, rows % 8 != 0 (the last group from the tail), a pool
    # past the DMA ring, one past a step's slots
    ((3, 203, 1000, 17), (1, 2 * RING + 3, 7, 1100), 21, 128),
    # d = 64 tables, read d-major; one-hot bags in one step
    ((130, 5), (1, 9), 700, 64),
], ids=["dlrmv2-like", "d64"])
def test_eb_kernel_ragged_matches_gather_sum(rng, rows, pools, bags, d):
    """Tables of unequal rows and pools in one kernel call, against an XLA
    gather-sum per table: −1 slots add nothing, weights scale each row,
    each table's last row is read; and the lookup counts as many
    ``eb_slots`` as it has bag positions."""
    from repro.layers.common import Ctx
    from repro.layers.embedding import embedding_bag_fwd
    from repro.protect import ProtectionPlan
    offs = np.cumsum((0,) + rows)
    t, al, be = _eb_inputs(rng, (), int(offs[-1]), d)
    idx, w = [], []
    for i, (n, p) in enumerate(zip(rows, pools)):
        x = rng.integers(0, n, size=(bags, p))
        x[rng.uniform(size=x.shape) < 0.2] = -1
        x[-1, -1] = n - 1
        idx.append(jnp.asarray(np.where(x >= 0, x + offs[i], -1), jnp.int32))
        w.append(jnp.asarray(rng.uniform(-2.0, 2.0, size=x.shape),
                             jnp.float32))
    r, rsum = abft_eb_pallas(t, al, be, tuple(idx), tuple(w), interpret=True)
    assert r.shape == (len(rows), bags, d) and rsum.shape == (len(rows), bags)
    for i in range(len(rows)):
        r_ref, rsum_ref = kref.abft_eb_ref(t, al, be, idx[i], w[i])
        np.testing.assert_allclose(np.asarray(r[i]), np.asarray(r_ref),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(rsum[i]), np.asarray(rsum_ref),
                                   rtol=1e-4, atol=1e-3)
    from repro.core.abft_embedding import table_rowsums
    p = {"table": t, "alphas": al, "betas": be, "rowsums": table_rowsums(t)}
    ctx = Ctx(plan=ProtectionPlan.parse("*:policy=log:scheme=pallas"))
    got, rep = embedding_bag_fwd(p, tuple(idx), ctx, tuple(w))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(r.astype(got.dtype), np.float32))
    assert int(rep.eb_slots) == bags * sum(pools)
    assert int(rep.checks["embedding_bag"]) == len(rows)
    assert int(rep.errors["embedding_bag"]) == 0


@pytest.mark.parametrize("bags,pools", [(512, (3, 1, 100, 27, 1100)),
                                        (7, (1, 2))])
def test_ragged_steps_cover_every_bag_once(bags, pools):
    """The ragged grid: each table's bags in order, in steps of at most
    STEP_SLOTS slots (one bag where its pool alone is more), the slots of
    every step one after the other."""
    from repro.kernels.abft_embeddingbag import STEP_SLOTS, ragged_steps
    steps = ragged_steps(bags, pools)
    slot = 0
    for t, pool in enumerate(pools):
        mine = steps[steps[:, 0] == t]
        assert (mine[:, 3] == pool).all()
        assert list(mine[:, 1]) == list(np.cumsum([0] + list(mine[:-1, 2])))
        assert mine[:, 2].sum() == bags
        assert (mine[:, 2] * pool <= max(STEP_SLOTS, pool)).all()
        for _, _, nb, _, base in mine:
            assert base == slot
            slot += nb * pool
    assert slot == bags * sum(pools)


def test_grid_slots_count_what_the_grid_runs(monkeypatch):
    """``eb_slots`` is read off the kernel's grid: every bag position once
    (the last bag block runs only the bags it holds), and a grid that
    padded each bag to the largest pool would count its padding."""
    from repro.kernels import abft_embeddingbag as eb
    from repro.kernels.ops import eb_slots
    pools = (3, 1, 100, 27)
    ragged = tuple(jnp.zeros((512, p), jnp.int32) for p in pools)
    assert eb.grid_slots(ragged, 128) == 512 * 131
    assert eb_slots(ragged, 128, use_pallas=False) == 512 * 131
    for bags, pool, d in ((2500, 1, 128), (19, 45, 64), (21, 100, 128)):
        assert eb.grid_slots(jnp.zeros((bags, pool), jnp.int32), d) \
            == bags * pool
    steps = eb.ragged_steps
    monkeypatch.setattr(eb, "ragged_steps", lambda bags, p: steps(
        bags, [max(p)] * len(p)))
    assert eb.grid_slots(ragged, 128) == 512 * 100 * len(pools)


def test_eb_ops_end_to_end_detection(rng):
    from repro.core.abft_embedding import table_rowsums
    t = jnp.asarray(rng.integers(-128, 128, size=(128, 64)), jnp.int8)
    al = jnp.asarray(rng.uniform(0.01, 0.1, size=128), jnp.float32)
    be = jnp.asarray(rng.uniform(-0.1, 0.1, size=128), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 128, size=(4, 20)), jnp.int32)
    cs = table_rowsums(t)
    out = ops.abft_embedding_bag(t, al, be, idx, cs, use_pallas=True)
    assert int(out.err_count) == 0
    # corrupt a *read* row's high bit => Eq. 5 must trip
    row = int(idx[0, 0])
    t_bad = t.at[row, 5].set(t[row, 5] ^ np.int8(np.uint8(0x80).view(np.int8)))
    out_bad = ops.abft_embedding_bag(t_bad, al, be, idx, cs,
                                     use_pallas=True)
    assert int(out_bad.err_count) >= 1


# ---------------------------- quantize_rows --------------------------------

@pytest.mark.parametrize("m,n", [(4, 64), (128, 128), (65, 300), (1, 12288)])
def test_quantize_rows_matches_ref(rng, m, n):
    x = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    q_ref, a_ref, b_ref = kref.quantize_rows_ref(x)
    q, a, b = quantize_rows_pallas(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_ref), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_rows_dtypes(rng, dtype):
    x = jnp.asarray(rng.normal(size=(8, 256)), dtype)
    q, a, b = quantize_rows_pallas(x, interpret=True)
    recon = np.asarray(a)[:, None] * np.asarray(q, np.float32) + \
        np.asarray(b)[:, None]
    np.testing.assert_allclose(recon, np.asarray(x, np.float32), atol=0.02)
