"""The repro.protect subsystem: plan parsing/resolution, ProtectedOp
adapters, per-op policy application, the generalized FaultReport under
jit/scan/vmap, and protect(apply_fn, plan) on a real model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import policy
from repro.core.inject import random_bitflip
from repro.protect import (Check, OpRule, ProtectionPlan, default_plan,
                           encode_tree, get_op, protect, protected_call,
                           unprotected_plan)
from repro.protect.plan import ResolvedRule
from repro.protect.runtime import rule_for


# ------------------------------ plan ----------------------------------------

def test_plan_parse_round_trip():
    text = ("*:policy=log,embedding_bag:off,"
            "qgemm/attn.*:policy=recompute:retries=2,"
            "embedding_bag:rel_bound=0.0001")
    plan = ProtectionPlan.parse(text)
    assert len(plan.rules) == 4
    back = ProtectionPlan.from_dict(plan.to_dict())
    assert back == plan
    assert "qgemm/attn.*" in plan.describe()


def test_plan_resolution_precedence_and_paths():
    for scheme in ("xla", "pallas"):
        plan = ProtectionPlan.parse(
            "*:policy=log,qgemm:policy=recompute,"
            f"qgemm/attn.*:scheme={scheme},qgemm/attn.wq:off")
        r = plan.resolve("qgemm", "mlp.up")
        assert r.enabled and r.policy == "recompute" and r.scheme is None
        r = plan.resolve("qgemm", "attn.wk")
        assert r.enabled and r.scheme == scheme and r.policy == "recompute"
        assert not plan.resolve("qgemm", "attn.wq").enabled
        # unrelated op inherits only the wildcard
        assert plan.resolve("embedding_bag", "tables").policy == "log"


def test_plan_parse_rejects_garbage():
    with pytest.raises(ValueError):
        ProtectionPlan.parse("qgemm:policy=sacrifice")
    with pytest.raises(ValueError):
        ProtectionPlan.parse("qgemm:frobnicate")
    with pytest.raises(ValueError):
        ProtectionPlan.parse("qgemm:rel_bound=not_a_float")


def test_plan_bare_on_off_and_empty():
    assert not ProtectionPlan.parse("off").resolve("qgemm").enabled
    assert ProtectionPlan.parse("").resolve("qgemm").enabled
    assert not unprotected_plan().resolve("embedding_bag").enabled
    d = default_plan()
    assert d.resolve("qgemm").enabled
    assert not d.resolve("kv_cache").enabled
    assert not d.resolve("float_gemm").enabled


def test_opt_in_ops_stay_off_in_parsed_plans():
    # a parse()-built plan must not silently enable the opt-in kinds —
    # same string, same behavior as default_plan-seeded entry points
    p = ProtectionPlan.parse("*:policy=recompute")
    assert p.resolve("qgemm").enabled
    assert not p.resolve("kv_cache").enabled
    assert not p.resolve("float_gemm").enabled
    # ...but an explicit rule (or explicit wildcard on/off) opts in
    assert ProtectionPlan.parse("kv_cache:on").resolve("kv_cache").enabled
    assert ProtectionPlan.parse("*:on").resolve("kv_cache").enabled


def test_plan_is_hashable_and_ctx_embeddable():
    from repro.layers.common import Ctx
    plan = ProtectionPlan.parse("*:policy=recompute")
    hash(plan)
    ctx = Ctx(quant=True, plan=plan)
    assert rule_for(ctx, "qgemm").policy == "recompute"


def test_rule_for_legacy_flags():
    from repro.layers.common import Ctx
    assert rule_for(Ctx(abft=True), "qgemm").enabled
    assert not rule_for(Ctx(abft=False), "embedding_bag").enabled
    assert not rule_for(Ctx(abft=True), "kv_cache").enabled
    assert rule_for(Ctx(float_abft=True), "float_gemm").enabled
    assert not rule_for(Ctx(), "float_gemm").enabled


# --------------------------- adapters ---------------------------------------

def _gemm_fixture(m=8, k=64, n=32):
    ka, kb = jax.random.split(jax.random.key(0))
    a = jax.random.randint(ka, (m, k), 0, 256, jnp.uint8)
    b = jax.random.randint(kb, (k, n), -127, 128, jnp.int8)
    return a, b, get_op("qgemm").encode(b)


def test_qgemm_adapter_schemes_detect_flip():
    a, b, packed = _gemm_fixture()
    n = b.shape[1]
    b_bad = random_bitflip(jax.random.key(7), b)
    bad_packed = jnp.concatenate([b_bad, packed[:, n:]], axis=1)
    qg = get_op("qgemm")
    assert qg.schemes == get_op("embedding_bag").schemes == ("xla", "pallas")
    for scheme in ("xla", "pallas"):
        _, check = qg(packed, a, rule=ResolvedRule(scheme=scheme))
        assert int(check.err_count) == 0, scheme
        _, check = qg(bad_packed, a, rule=ResolvedRule(scheme=scheme))
        assert int(check.err_count) > 0, scheme
    # unprotected baseline matches the protected C
    c, _ = qg(packed, a)
    np.testing.assert_array_equal(np.asarray(qg.unprotected(packed, a)),
                                  np.asarray(c))


@pytest.mark.parametrize("scheme", ["packed", "unfused"])
def test_qgemm_adapter_refuses_other_schemes(scheme):
    a, _, packed = _gemm_fixture()
    with pytest.raises(ValueError,
                       match=r"qgemm scheme .*\('xla', 'pallas'\)"):
        get_op("qgemm")(packed, a, rule=ResolvedRule(scheme=scheme))


def test_eb_adapter_rel_bound_changes_detection():
    kt, ki = jax.random.split(jax.random.key(1))
    table = jax.random.randint(kt, (512, 64), -128, 128, jnp.int8)
    alphas = jnp.full((512,), 1e-2, jnp.float32)
    betas = jnp.full((512,), 0.5, jnp.float32)
    eb = get_op("embedding_bag")
    enc = eb.encode((table, alphas, betas))
    idx = jax.random.randint(ki, (4, 20), 0, 512, jnp.int32)
    # low-bit corruption on an accessed element
    row = int(idx[0, 0])
    bad = (table.at[row, 3].add(1),) + enc[1:]
    _, tight = eb(bad, idx, rule=ResolvedRule(rel_bound=1e-9))
    _, loose = eb(bad, idx, rule=ResolvedRule(rel_bound=1e-1))
    assert int(tight.err_count) >= 1
    assert int(loose.err_count) == 0


def test_kv_adapter_verify_and_attend():
    kv = get_op("kv_cache")
    b, kvh, s, dh = 2, 2, 16, 8
    kx = jax.random.normal(jax.random.key(2), (b, kvh, s, dh))
    vx = jax.random.normal(jax.random.key(3), (b, kvh, s, dh))
    enc = kv.encode((kx, vx))
    q = jax.random.normal(jax.random.key(4), (b, 4, dh))
    pos = jnp.full((b,), s - 1, jnp.int32)
    out, check = kv(enc, q, pos, n_heads=4, n_kv=kvh)
    assert out.shape == (b, 4, dh) and int(check.err_count) == 0
    qk = np.asarray(enc[0].q).copy()
    qk[0, 0, 3, 0] ^= 0x40
    bad_k = enc[0]._replace(q=jnp.asarray(qk))
    _, check2 = kv((bad_k, enc[1]), q, pos, n_heads=4, n_kv=kvh)
    assert int(check2.err_count) >= 1


# ---------------------- protected_call + policies ---------------------------

def test_protected_call_disabled_runs_baseline():
    a, b, packed = _gemm_fixture()
    c, rep = protected_call("qgemm", packed, a,
                            rule=ResolvedRule(enabled=False))
    assert int(rep.total_checks()) == 0 and int(rep.total_errors()) == 0
    np.testing.assert_array_equal(
        np.asarray(c), np.asarray(get_op("qgemm").unprotected(packed, a)))


def test_policy_recompute_counts_retries_via_plan():
    a, b, packed = _gemm_fixture()
    n = b.shape[1]
    bad = jnp.concatenate([random_bitflip(jax.random.key(5), b),
                           packed[:, n:]], axis=1)
    _, rep = protected_call("qgemm", bad, a,
                            rule=ResolvedRule(policy="recompute",
                                              max_retries=2))
    assert int(rep.retries) == 2          # deterministic sim: persists
    assert int(rep.errors["qgemm"]) > 0
    _, rep2 = protected_call("qgemm", packed, a,
                             rule=ResolvedRule(policy="recompute"))
    assert int(rep2.retries) == 0 and int(rep2.errors["qgemm"]) == 0


def test_policy_correct_repairs_single_row_weight_fault():
    # m=1 (DLRM's classic skinny GEMM): a weight flip corrupts exactly one
    # C cell, so the row+column checksums localize and repair it
    a, b, packed = _gemm_fixture(m=1)
    n = b.shape[1]
    b_bad = random_bitflip(jax.random.key(9), b)
    bad_packed = jnp.concatenate([b_bad, packed[:, n:]], axis=1)
    qg = get_op("qgemm")
    # expected C from clean weights
    c_clean = qg.unprotected(packed, a)
    c_corrupt = qg.unprotected(bad_packed, a)
    assert np.any(np.asarray(c_clean) != np.asarray(c_corrupt))
    # correction repairs C *relative to the operands it ran with*: here we
    # emulate an accumulator upset by handing correct() the clean col aux
    _, check = qg(bad_packed, a, rule=ResolvedRule(policy="correct"))
    col_clean = jax.lax.dot_general(
        jnp.sum(a.astype(jnp.int32), axis=0), b.astype(jnp.int32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    fixed, residual, applied = qg.correct(
        c_corrupt, Check(check.err_count, check.err_mask, col_clean))
    assert int(applied) == 1 and int(residual) == 0
    np.testing.assert_array_equal(np.asarray(fixed), np.asarray(c_clean))


def test_policy_correct_end_to_end_on_accumulator_fault():
    """The correct policy behind protected_call: a custom adapter whose
    run corrupts C after the dot (an accumulator upset, §IV-C2) — the
    colcheck threaded through kernels.ops repairs it."""
    from repro.kernels import ops as kops
    from repro.protect import register_op
    from repro.protect.ops import QGemmOp
    from repro.core import verify_rows

    class UpsetQGemm(QGemmOp):
        name = "qgemm_upset"

        def __call__(self, encoded, a_q, *, rule=ResolvedRule()):
            c, _, col_check = kops.abft_qgemm(a_q, encoded,
                                              with_colcheck=True)
            c = c.at[2, 5].add(-4321)          # the upset
            n = encoded.shape[1] - self.lane
            # re-verify rows of the corrupted C against the fused column
            c_full = jax.lax.dot_general(
                a_q, encoded, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            err_rows, err = verify_rows(c, c_full[:, n])
            return c, Check(err, err_rows, col_check)

    register_op(UpsetQGemm())
    a, b, packed = _gemm_fixture()
    c, rep = protected_call("qgemm_upset", packed, a,
                            rule=ResolvedRule(policy="correct"))
    assert int(rep.corrections) == 1
    assert int(rep.errors["qgemm_upset"]) == 0
    np.testing.assert_array_equal(
        np.asarray(c), np.asarray(get_op("qgemm").unprotected(packed, a)))


def test_policy_correct_repairs_weight_flip_via_colsum():
    """(packed, colsum_ref) tuple encoding: a weight flip poisons a whole
    C column (m > 1), which the single-cell accumulator repair declines —
    the B-side column-sum reference localizes and repairs it end to end
    through protected_call."""
    from repro.core.abft_gemm import encode_weight_colsum

    a, b, packed = _gemm_fixture()
    colsum = encode_weight_colsum(b)
    n = b.shape[1]
    bq = np.asarray(b).copy()
    bq[5, 7] ^= np.int8(0x20)
    bad_packed = jnp.concatenate([jnp.asarray(bq), packed[:, n:]], axis=1)
    c, rep = protected_call("qgemm", (bad_packed, colsum), a,
                            rule=ResolvedRule(policy="correct"))
    assert int(rep.corrections) == 1
    assert int(rep.errors["qgemm"]) == 0
    np.testing.assert_array_equal(
        np.asarray(c), np.asarray(get_op("qgemm").unprotected(packed, a)))
    # without the colsum reference the same fault is detected but not
    # repairable: it falls through with residual errors
    _, rep2 = protected_call("qgemm", bad_packed, a,
                             rule=ResolvedRule(policy="correct"))
    assert int(rep2.corrections) == 0
    assert int(rep2.errors["qgemm"]) > 0


def test_qlinear_correct_policy_repairs_weight_flip():
    """The layer wiring: a correct-policy call site hands the stored
    colsum over as the repair reference, so a flipped packed weight
    yields the clean activations plus one recorded correction."""
    from repro.layers.common import Ctx
    from repro.layers.linear import init_qlinear, qlinear

    p = init_qlinear(jax.random.key(3), 32, 16, bias=False)
    p = {k: v.value for k, v in p.items()}
    x = jax.random.normal(jax.random.key(4), (4, 32))
    plan = ProtectionPlan.parse("*:policy=correct")
    y_clean, rep0 = qlinear(p, x, Ctx(quant=True, plan=plan))
    assert int(rep0.total_errors()) == 0 and int(rep0.corrections) == 0
    bad = dict(p)
    w = np.asarray(p["w_packed"]).copy()
    w[7, 5] ^= np.int8(0x10)             # payload flip; refs stay clean
    bad["w_packed"] = jnp.asarray(w)
    y_bad, rep = qlinear(bad, x, Ctx(quant=True, plan=plan))
    assert int(rep.corrections) == 1
    assert int(rep.total_errors()) == 0
    np.testing.assert_array_equal(np.asarray(y_bad), np.asarray(y_clean))


def test_policy_correct_falls_back_to_recompute_for_eb():
    kt, ki = jax.random.split(jax.random.key(6))
    table = jax.random.randint(kt, (256, 32), -128, 128, jnp.int8)
    alphas = jnp.full((256,), 1e-2, jnp.float32)
    betas = jnp.zeros((256,), jnp.float32)
    eb = get_op("embedding_bag")
    enc = eb.encode((table, alphas, betas))
    idx = jax.random.randint(ki, (2, 8), 0, 256, jnp.int32)
    bad = (table.at[int(idx[0, 0]), 0].add(100),) + enc[1:]
    _, rep = protected_call("embedding_bag", bad, idx,
                            rule=ResolvedRule(policy="correct"))
    assert int(rep.retries) == 1          # fell back to detect->retry


def test_policy_abort_raises_through_jit():
    a, b, packed = _gemm_fixture()
    n = b.shape[1]
    bad = jnp.concatenate([random_bitflip(jax.random.key(11), b),
                           packed[:, n:]], axis=1)
    fn = jax.jit(lambda: protected_call(
        "qgemm", bad, a, rule=ResolvedRule(policy="abort"))[0])
    try:
        jax.block_until_ready(fn())
        raised = None
    except Exception as e:
        raised = e
    assert raised is not None and policy.is_fault_abort(raised)


# -------------------------- FaultReport pytree ------------------------------

def test_report_round_trips_under_jit_scan_vmap():
    def one(err):
        return policy.op_report("qgemm", err)

    rep = jax.jit(one)(jnp.asarray(3, jnp.int32))
    assert int(rep.errors["qgemm"]) == 3

    def body(carry, x):
        return policy.merge_reports(carry, one(x)), None

    final, _ = jax.lax.scan(body, policy.empty_report(),
                            jnp.arange(5, dtype=jnp.int32))
    assert int(final.errors["qgemm"]) == 10
    assert int(final.checks["qgemm"]) == 5

    reps = jax.vmap(one)(jnp.arange(4, dtype=jnp.int32))
    summed = jax.tree.map(jnp.sum, reps)
    assert int(summed.errors["qgemm"]) == 6


def test_report_keyed_metrics_and_legacy_aliases():
    rep = policy.merge_reports(
        policy.op_report("qgemm", 2),
        policy.op_report("embedding_bag", 1),
        policy.op_report("kv_cache", 4))
    m = rep.as_metrics()
    assert int(m["abft/qgemm_errors"]) == 2
    assert int(m["abft/embedding_bag_errors"]) == 1
    assert int(m["abft/kv_cache_errors"]) == 4
    # legacy names still resolve (pre-protect consumers)
    assert int(m["abft/gemm_errors"]) == 2
    assert int(m["abft/eb_errors"]) == 1
    assert int(rep.total_errors()) == 7


def test_report_unknown_kind_raises():
    with pytest.raises(KeyError):
        policy.op_report("not_registered", 1)


# --------------------------- protect(apply_fn) ------------------------------

@pytest.fixture(scope="module")
def small_model():
    from repro.configs.reduce import reduce_cfg
    from repro.configs.registry import get_arch
    from repro.models.base import build_model
    from repro.sharding import values_of

    cfg = reduce_cfg(get_arch("llama3.2-1b"))
    model = build_model(cfg, max_pos=128)
    params = values_of(model.init(jax.random.key(2), quant=True))
    tokens = jax.random.randint(jax.random.key(3), (2, 16), 0, cfg.vocab,
                                jnp.int32)
    return cfg, model, params, tokens


def _prefill(model, plan, params, tokens):
    pf = protect(model.prefill, plan)
    return jax.jit(lambda p, t: pf(p, {"tokens": t}, cache_len=32))(
        params, tokens)


def test_protect_plan_flips_eb_off_without_model_edits(small_model):
    cfg, model, params, tokens = small_model
    (l_on, _), rep_on = _prefill(model, default_plan(), params, tokens)
    (l_off, _), rep_off = _prefill(
        model, default_plan().with_rules(OpRule("embedding_bag",
                                                enabled=False)),
        params, tokens)
    assert int(rep_on.eb_checks) > 0
    assert int(rep_off.eb_checks) == 0
    assert int(rep_off.gemm_checks) == int(rep_on.gemm_checks)
    np.testing.assert_allclose(np.asarray(l_on, np.float32),
                               np.asarray(l_off, np.float32))


def test_protect_plan_policy_recompute_without_model_edits(small_model):
    cfg, model, params, tokens = small_model
    plan = default_plan().with_rules(OpRule("*", policy="recompute"))
    (_, _), rep = _prefill(model, plan, params, tokens)
    assert int(rep.retries) == 0          # clean run: cond never fires
    assert int(rep.gemm_checks) > 0


def test_protect_kv_cache_plan_decode(small_model):
    cfg, model, params, tokens = small_model
    plan = default_plan().with_rules(OpRule("kv_cache", enabled=True))
    pf = protect(model.prefill, plan)
    dec = protect(model.decode, plan)
    (logits, cache), _ = jax.jit(
        lambda p, t: pf(p, {"tokens": t}, cache_len=32))(params, tokens)
    from repro.protect.ops import QuantKV
    assert isinstance(cache["attn"]["k"], QuantKV)
    tok = jnp.argmax(logits[..., :cfg.vocab], -1).astype(jnp.int32)
    pos = jnp.full((2,), 16, jnp.int32)
    (l2, cache2), rep = jax.jit(dec)(params, cache, tok, pos)
    assert int(rep.checks["kv_cache"]) == cfg.n_layers
    assert int(rep.errors["kv_cache"]) == 0
    assert l2.shape[0] == 2


def test_protect_surfaces_nested_loss_report(small_model):
    # Model.loss nests its report: (loss, (metrics, rep)) — protect() must
    # surface the merged report, not a silent empty one
    cfg, model, params, tokens = small_model
    loss_p = protect(model.loss, default_plan())
    batch = {"tokens": tokens, "labels": tokens}
    out, rep = jax.jit(loss_p)(params, batch)
    loss, (metrics, inner_rep) = out
    assert int(rep.total_checks()) > 0
    assert int(rep.total_checks()) == int(inner_rep.total_checks())


def test_encode_tree_refreshes_colsum_with_lanes():
    # swapping the weight block inside w_packed then encode()ing must
    # refresh BOTH the checksum lanes and the Eq. 1 colsum constant —
    # a stale colsum is silent output corruption, not a detection miss
    from repro.layers.common import Ctx
    from repro.layers.linear import init_qlinear, qlinear

    p = init_qlinear(jax.random.key(0), 32, 16)
    p = {k: v.value for k, v in p.items()}
    new_w = jax.random.randint(jax.random.key(1), (32, 16), -127, 128,
                               jnp.int8)
    p["w_packed"] = jnp.concatenate([new_w, p["w_packed"][:, 16:]], axis=1)
    p2 = encode_tree(p)
    np.testing.assert_array_equal(
        np.asarray(p2["colsum"]),
        np.asarray(jnp.sum(new_w.astype(jnp.int32), axis=0), np.float32))
    x = jax.random.normal(jax.random.key(2), (4, 32))
    _, rep = qlinear(p2, x, Ctx(quant=True, plan=default_plan()))
    assert int(rep.total_errors()) == 0
    # reference: a fresh init from the same weight block gives the same y
    ref = encode_tree({"w_packed": p2["w_packed"], "alpha": p["alpha"],
                       "colsum": jnp.zeros_like(p["colsum"]),
                       "b": p["b"]})
    np.testing.assert_array_equal(np.asarray(ref["colsum"]),
                                  np.asarray(p2["colsum"]))


def test_encode_tree_refreshes_checksums(small_model):
    cfg, model, params, tokens = small_model
    # corrupt a packed weight, then re-encode: the fresh checksum matches
    # the corrupted weight again (zero detections)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    idx = max((i for i, l in enumerate(leaves)
               if l.dtype == jnp.int8 and l.ndim >= 2),
              key=lambda i: leaves[i].size)
    leaves[idx] = random_bitflip(jax.random.key(8), leaves[idx])
    bad_params = jax.tree_util.tree_unflatten(treedef, leaves)
    (_, _), rep_bad = _prefill(model, default_plan(), bad_params, tokens)
    reencoded = encode_tree(bad_params)
    (_, _), rep_fixed = _prefill(model, default_plan(), reencoded, tokens)
    assert int(rep_fixed.total_errors()) == 0
    # (the flip may or may not land in a checked op's weight block; the
    # invariant under test is that re-encoding always clears detections)
    assert int(rep_bad.total_errors()) >= int(rep_fixed.total_errors())
