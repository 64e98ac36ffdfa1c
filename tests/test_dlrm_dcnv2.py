"""DLRMv2 (DLRM-DCNv2) on the normal path: the served forward against the
plain reference, its checks, and the serving engine's DLRM lane."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dlrm import DCNV2, DlrmExtras
from repro.configs.registry import get_arch
from repro.models.dlrm import dlrm_forward, init_dlrm
from repro.models.dlrm_reference import dlrm_reference
from repro.protect import ProtectionPlan, default_plan, protect
from repro.serving import FaultInjection, ServingEngine, TenantSpec
from repro.serving.workload import Request
from repro.sharding import values_of

#: the kernels of the chip's path, in interpret mode on the CPU
PALLAS = "*:policy=log,qgemm:scheme=pallas,embedding_bag:scheme=pallas"
#: widest |served − reference| ÷ rms(reference) the served forward may
#: show.  The reference is float32 throughout; the served forward quantizes
#: each GEMM's input rows to int8 and computes in bfloat16 between GEMMs,
#: and three cross layers multiply by x_0, so the gap is wide: seeds 0-5 of
#: this size read 0.09-0.57 on the CPU (0.09-0.29 in float32, so most of it
#: is the int8 activations).  The int4 control read 2.9-9.8.
TOLERANCE = 1.0


def small(seed: int, batch: int = 8) -> DlrmExtras:
    """26 tables of 3 to 4,099 rows at d = 128, pools 1 to 100."""
    rng = np.random.default_rng(seed)
    return dataclasses.replace(
        DCNV2, bottom_mlp=(64, 128), top_mlp=(64, 32, 1), cross_rank=64,
        batch=batch,
        table_rows=(3,) + tuple(int(r) for r in rng.integers(8, 4100, 25)),
        pooling=(100,) + tuple(int(p) for p in rng.integers(1, 28, 25)))


def inputs(ex: DlrmExtras, seed: int):
    rng = np.random.default_rng(seed + 100)
    dense = rng.standard_normal((ex.batch, ex.n_dense)).astype(np.float32)
    bags = np.concatenate([rng.integers(0, r, (ex.batch, p))
                           for r, p in zip(ex.rows, ex.pools)], axis=1)
    return dense, bags.astype(np.int32)


def gap(got, want) -> float:
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / np.sqrt(np.mean(want ** 2)))


@pytest.fixture(scope="module")
def model():
    ex = small(0)
    params = values_of(jax.jit(functools.partial(init_dlrm, ex=ex))(
        jax.random.key(0)))
    return ex, params


def test_forward_matches_the_reference_and_int4_does_not(model):
    ex, params = model
    dense, bags = inputs(ex, 0)
    bags[0, 0] = -1                          # a pad in the pool-100 table
    fwd = jax.jit(protect(functools.partial(dlrm_forward, ex=ex),
                          ProtectionPlan.parse(PALLAS),
                          compute_dtype=jnp.bfloat16))
    logit, rep = fwd(params, dense, bags)
    want = dlrm_reference(params, dense, bags, ex)
    assert gap(logit, want) <= TOLERANCE
    assert gap(dlrm_reference(params, dense, bags, ex, bits=4),
               want) > TOLERANCE
    assert int(rep.total_errors()) == 0


def test_default_plan_checks_every_gemm_and_table(model):
    """``OpRule("*")`` protects the six cross GEMMs too: 2 bottom + 6
    cross + 3 top int8 GEMMs, one EmbeddingBag check a table, and the
    lookups' slots counted exactly (no bag padded to another's pool)."""
    ex, params = model
    dense, bags = inputs(ex, 1)
    _, rep = jax.jit(protect(functools.partial(dlrm_forward, ex=ex),
                             default_plan()))(params, dense, bags)
    m = {k: int(v) for k, v in rep.as_metrics().items()}
    assert m["abft/qgemm_checks"] == 2 + 2 * ex.cross_layers + 3
    assert m["abft/embedding_bag_checks"] == ex.n_tables
    assert m["abft/eb_slots"] == ex.batch * sum(ex.pools)
    no_cross = ProtectionPlan.parse("*:policy=log,qgemm/cross.*:off")
    _, rep = jax.jit(protect(functools.partial(dlrm_forward, ex=ex),
                             no_cross))(params, dense, bags)
    assert int(rep.checks["qgemm"]) == 2 + 3


def test_a_flip_in_a_cross_weight_is_detected(model):
    ex, params = model
    dense, bags = inputs(ex, 2)
    packed = params["cross"]["w"]["w_packed"]
    bad = jax.tree.map(lambda x: x, params)
    bad["cross"]["w"]["w_packed"] = packed.at[1, 3, 5].add(jnp.int8(64))
    _, rep = jax.jit(protect(functools.partial(dlrm_forward, ex=ex),
                             default_plan()))(bad, dense, bags)
    assert int(rep.errors["qgemm"]) >= 1


def test_two_row_halves_add_up_to_the_uncut_lookup():
    """The deployment the cell stands for splits a large table by rows over
    two chips: each chip looks up the ids that fall in its half (the others
    are −1 pads) and the two partial bag sums add up to the uncut lookup,
    each half passing its own Eq. (5) check."""
    from repro.core.abft_embedding import table_rowsums
    from repro.kernels.ops import abft_embedding_bag
    rng = np.random.default_rng(7)
    rows, half, pools, bags = (3, 1003), 501, (1, 100), 8
    table = rng.integers(-128, 128, (sum(rows), 128)).astype(np.int8)
    alphas = rng.uniform(0.001, 0.1, sum(rows)).astype(np.float32)
    betas = rng.uniform(-0.5, 0.5, sum(rows)).astype(np.float32)
    small_ids = rng.integers(0, rows[0], (bags, pools[0]))
    big_ids = rng.integers(0, rows[1], (bags, pools[1]))

    def lookup(lo, hi, ids):
        """The tables [lo, hi) of the concatenated rows; ids into them."""
        t = jnp.asarray(table[lo:hi])
        out = abft_embedding_bag(
            t, jnp.asarray(alphas[lo:hi]), jnp.asarray(betas[lo:hi]),
            tuple(jnp.asarray(i, jnp.int32) for i in ids), table_rowsums(t),
            use_pallas=True)
        assert int(out.err_count) == 0
        return np.asarray(out.r)

    uncut = lookup(0, sum(rows), (small_ids, big_ids + rows[0]))
    mine = np.where(big_ids < half, big_ids + rows[0], -1)
    first = lookup(0, rows[0] + half, (small_ids, mine))
    theirs = np.where(big_ids >= half, big_ids - half, -1)
    (second,) = lookup(rows[0] + half, sum(rows), (theirs,))
    np.testing.assert_array_equal(first[0], uncut[0])
    np.testing.assert_allclose(first[1] + second, uncut[1], rtol=1e-5,
                               atol=1e-4)


def test_published_configuration():
    assert DCNV2.ragged and sum(DCNV2.pools) == 214
    assert sum(DCNV2.rows) == 204_184_588
    assert DCNV2.inter_dim == 3456
    with pytest.raises(ValueError):
        dataclasses.replace(DCNV2, pooling=(1, 2))
    with pytest.raises(ValueError):
        dataclasses.replace(DCNV2, interaction="cat")


def test_engine_serves_multi_hot_requests():
    """The serving engine's DLRM lane takes the [batch, Σ pool] payload as
    one int32 array, puts the step's slot count on its event without an
    extra read per step, and catches a flip injected into the cross
    network."""
    ex = dataclasses.replace(small(3, batch=4), n_tables=3,
                             table_rows=(3, 50, 2000), pooling=(1, 9, 40),
                             cross_layers=2)
    eng = ServingEngine(get_arch("dlrm"), [TenantSpec("t")], dlrm_extras=ex,
                        seed=0)

    def req(i):
        dense, bags = inputs(ex, i)
        return Request(rid=i, tenant="t", arrival_s=0.0, kind="dlrm",
                       prompt_len=0, max_new_tokens=0,
                       payload={"dense": dense, "bags": bags})

    eng.warmup(req(0))
    tel = eng.run([req(1), req(2)], warmup=False)
    for ev in tel.steps:
        assert ev.counters["eb_slots"] == 4 * 50
        assert ev.counters["qgemm_checks"] == 2 + 4 + 3
        assert ev.host_fetches == tel.steps[0].host_fetches
    assert sum(ev.errors for ev in tel.steps) == 0
    tel = eng.run([req(3)], warmup=False, inject=[
        FaultInjection(step=eng.global_step, victim="cross", seed=1)])
    (inj,) = tel.summary()["faults"]["injections"]
    assert inj["victim"].startswith("cross.") and inj["detected"]
