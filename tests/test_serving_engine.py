"""ServingEngine: per-tenant plan lanes, FaultReport merging across
interleaved prefill/decode under jit, online fault injection (transient
restore), and abort-policy request failure."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduce_cfg
from repro.configs.registry import get_arch
from repro.protect import ProtectionPlan, protect, merge_reports
from repro.serving import (FaultInjection, ServingEngine, TenantSpec,
                           chat_stream)

N_SLOTS = 2
MAX_PROMPT = 8
MAX_NEW = 4


@pytest.fixture(scope="module")
def engine():
    cfg = reduce_cfg(get_arch("llama3.2-1b"))
    tenants = [
        TenantSpec("premium", ProtectionPlan.parse(
            "*:policy=recompute,kv_cache:on", name="premium")),
        TenantSpec("standard", ProtectionPlan.parse(
            "*:policy=log", name="standard"), weight=2.0),
    ]
    eng = ServingEngine(cfg, tenants, n_slots=N_SLOTS,
                        max_prompt=MAX_PROMPT, max_new_tokens=MAX_NEW,
                        seed=0)
    eng.warmup()
    return eng


def _stream(n, seed=0, rate=500.0, arrival="poisson"):
    return chat_stream(n, tenants={"premium": 1.0, "standard": 2.0},
                       rate_rps=rate, arrival=arrival, seed=seed,
                       mean_prompt=6, max_prompt=MAX_PROMPT,
                       mean_output=3, max_output=MAX_NEW)


def test_lanes_group_tenants_by_plan(engine):
    assert len(engine.lanes) == 2
    lanes = {next(iter(lane.tenants)): lane for lane in engine.lanes}
    assert lanes["premium"] is not lanes["standard"]
    # same-plan tenants share a lane
    cfg = reduce_cfg(get_arch("llama3.2-1b"))
    p = ProtectionPlan.parse("*:policy=log")
    eng = ServingEngine(cfg, [TenantSpec("a", p), TenantSpec("b", p)],
                        n_slots=1, max_prompt=4, max_new_tokens=1)
    assert len(eng.lanes) == 1
    assert eng.lanes[0].tenants == {"a", "b"}


def test_run_completes_all_requests_and_slots_drain(engine):
    engine.reset_state()
    stream = _stream(8, seed=1)
    tel = engine.run(stream)
    assert len(tel.requests) == 8
    assert {r.rid for r in tel.requests} == set(range(8))
    assert all(not r.aborted for r in tel.requests)
    for lane in engine.lanes:
        assert lane.batcher.occupancy() == 0
        lane.batcher.check_invariants()
    s = tel.summary()
    assert set(s["per_tenant"]) <= {"premium", "standard"}
    for t in s["per_tenant"].values():
        assert t["completed"] == t["requests"]
        assert np.isfinite(t["ttft_ms"]["p99"])
    # every request got exactly the tokens it asked for
    by_rid = {r.rid: r for r in stream}
    for r in tel.requests:
        assert r.tokens_out == by_rid[r.rid].max_new_tokens
        assert r.first_token_s is not None
        assert r.finish_s >= r.first_token_s >= r.arrival_s


def test_fault_report_merging_interleaved_prefill_decode_under_jit():
    """The engine telemetry path sums per-step op-keyed counters; one
    jitted program interleaving prefill + decode with merged reports must
    agree with that sum exactly."""
    cfg = reduce_cfg(get_arch("llama3.2-1b"))
    plan = ProtectionPlan.parse("*:policy=log,kv_cache:on")
    from repro.models.base import build_model
    from repro.sharding import values_of

    cache_len = 16
    model = build_model(cfg, max_pos=cache_len + 8)
    params = values_of(jax.jit(
        lambda k: model.init(k, quant=True))(jax.random.key(0)))
    prefill_p = protect(model.prefill, plan, compute_dtype=jnp.bfloat16)
    decode_p = protect(model.decode, plan, compute_dtype=jnp.bfloat16)
    batch = {"tokens": jnp.zeros((1, 4), jnp.int32)}
    pos0 = jnp.asarray([4], jnp.int32)

    @jax.jit
    def stepwise(params, batch):
        (logits, cache), r1 = prefill_p(params, batch,
                                        cache_len=cache_len)
        tok = jnp.argmax(logits[..., :cfg.vocab], -1).astype(jnp.int32)
        (l2, cache), r2 = decode_p(params, cache, tok, pos0)
        tok2 = jnp.argmax(l2[..., :cfg.vocab], -1).astype(jnp.int32)
        (l3, cache), r3 = decode_p(params, cache, tok2, pos0 + 1)
        return [r.as_metrics() for r in (r1, r2, r3)], \
            merge_reports(r1, r2, r3).as_metrics()

    per_step, merged = stepwise(params, batch)
    from repro.core.policy import op_kinds
    for kind in op_kinds():
        for col in ("checks", "errors"):
            key = f"abft/{kind}_{col}"
            assert int(merged[key]) == sum(int(m[key]) for m in per_step)
    assert int(merged["abft/qgemm_checks"]) > 0
    assert int(merged["abft/kv_cache_checks"]) > 0


def test_engine_step_counters_consistent_across_interleaving(engine):
    engine.reset_state()
    tel = engine.run(_stream(6, seed=2))
    decode_checks = {}
    for ev in tel.steps:
        assert ev.kind in ("prefill", "decode")
        assert ev.counters.get("qgemm_checks", 0) > 0
        if ev.kind == "decode":
            # per-lane decode programs are fixed — identical check counts
            decode_checks.setdefault(ev.lane, set()).add(
                ev.counters["qgemm_checks"])
    for lane, counts in decode_checks.items():
        assert len(counts) == 1, (lane, counts)
    totals = tel.fault_counters()
    assert totals["qgemm_checks"] == sum(
        ev.counters["qgemm_checks"] for ev in tel.steps)
    assert totals["qgemm_errors"] == 0


def test_transient_injection_detected_and_weight_restored(engine):
    engine.reset_state()
    before = [np.asarray(x).copy() for x in jax.tree.leaves(engine.params)]
    tel = engine.run(_stream(8, seed=3),
                     inject=[FaultInjection(step=2, victim="mlp.down",
                                            seed=0)])
    after = jax.tree.leaves(engine.params)
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, np.asarray(a))
    (inj,) = tel.summary()["faults"]["injections"]
    assert "mlp.down" in inj["victim"]
    assert inj["detected"] and inj["latency_steps"] == 0
    flagged = [ev.step for ev in tel.steps if ev.errors > 0]
    assert flagged and all(s == inj["step"] for s in flagged)


def test_persistent_injection_restored_only_at_reset(engine):
    engine.reset_state()
    before = [np.asarray(x).copy() for x in jax.tree.leaves(engine.params)]
    engine.run(_stream(4, seed=4),
               inject=[FaultInjection(step=1, victim="attn.wq", seed=1,
                                      persistent=True)])
    changed = any(
        not np.array_equal(b, np.asarray(a))
        for b, a in zip(before, jax.tree.leaves(engine.params)))
    assert changed
    engine.reset_state()
    for b, a in zip(before, jax.tree.leaves(engine.params)):
        np.testing.assert_array_equal(b, np.asarray(a))


def test_abort_policy_fails_requests_not_server():
    cfg = reduce_cfg(get_arch("llama3.2-1b"))
    eng = ServingEngine(cfg, [TenantSpec("t", ProtectionPlan.parse(
        "*:policy=abort", name="abortive"))], n_slots=2,
        max_prompt=MAX_PROMPT, max_new_tokens=MAX_NEW, seed=0)
    stream = chat_stream(6, tenants={"t": 1.0}, rate_rps=500.0, seed=5,
                         mean_prompt=6, max_prompt=MAX_PROMPT,
                         mean_output=3, max_output=MAX_NEW)
    tel = eng.run(stream, inject=[FaultInjection(step=2, victim="mlp.down",
                                                 seed=0)])
    recs = {r.rid: r for r in tel.requests}
    assert len(recs) == 6                    # the server survived
    assert any(r.aborted for r in tel.requests)
    assert any(not r.aborted for r in tel.requests)
    for lane in eng.lanes:
        assert lane.batcher.occupancy() == 0


def test_bounded_queue_sheds_load_into_telemetry():
    cfg = reduce_cfg(get_arch("llama3.2-1b"))
    eng = ServingEngine(cfg, [TenantSpec("t", ProtectionPlan.parse(
        "*:policy=log"))], n_slots=1, max_prompt=MAX_PROMPT,
        max_new_tokens=MAX_NEW, queue_depth=1, seed=0)
    # a hard burst: everyone arrives at t=0 into 1 slot + depth-1 queue
    stream = chat_stream(10, tenants={"t": 1.0}, rate_rps=1e6, seed=6,
                         mean_prompt=6, max_prompt=MAX_PROMPT,
                         mean_output=3, max_output=MAX_NEW)
    tel = eng.run(stream)
    assert len(tel.requests) == 10           # shed requests recorded too
    ts = tel.summary()["per_tenant"]["t"]
    assert ts["rejected"] > 0
    assert ts["completed"] + ts["rejected"] == 10
    # rejected requests carry no latency samples
    for r in tel.requests:
        if r.rejected:
            assert r.first_token_s is None and r.tokens_out == 0


def test_stacked_persistent_and_transient_injections_restore(engine):
    engine.reset_state()
    before = [np.asarray(x).copy() for x in jax.tree.leaves(engine.params)]
    engine.run(_stream(8, seed=7), inject=[
        FaultInjection(step=1, victim="mlp.down", seed=0,
                       persistent=True),
        FaultInjection(step=3, victim="mlp.down", seed=1),   # transient
    ])
    # the transient was restored, the persistent fault survives it
    changed = any(
        not np.array_equal(b, np.asarray(a))
        for b, a in zip(before, jax.tree.leaves(engine.params)))
    assert changed
    engine.reset_state()
    for b, a in zip(before, jax.tree.leaves(engine.params)):
        np.testing.assert_array_equal(b, np.asarray(a))


def test_unknown_tenant_rejected(engine):
    engine.reset_state()
    bad = chat_stream(1, tenants={"nosuch": 1.0}, rate_rps=1.0, seed=0)
    with pytest.raises(ValueError, match="unknown tenant"):
        engine.run(bad)


def test_dlrm_pallas_and_xla_lanes_agree_and_catch_a_flip():
    """One-shot DLRM lookups through the fused Pallas kernels (interpret
    mode here) and through the XLA reference, on the same tables: equal
    logits per request, no flag on the clean pass, and a bottom-MLP weight
    flip detected — what ``chip_smoke.py`` checks on the chip, at a size
    the CPU runs in seconds."""
    import dataclasses

    from repro.configs.dlrm import EXTRAS
    from repro.serving import dlrm_stream

    ex = dataclasses.replace(EXTRAS, n_tables=4, table_rows=4096,
                             pooling=20, bottom_mlp=(64, 128),
                             top_mlp=(64, 32, 1))
    tenants = [
        TenantSpec("pallas", ProtectionPlan.parse(
            "*:policy=log,qgemm:scheme=pallas,embedding_bag:scheme=pallas",
            name="pallas")),
        TenantSpec("xla", ProtectionPlan.parse(
            "*:policy=log,qgemm:scheme=xla,embedding_bag:scheme=xla",
            name="xla")),
    ]
    eng = ServingEngine(get_arch("dlrm"), tenants, dlrm_extras=ex, seed=0)
    n = 3
    base = dlrm_stream(n, tenants={"pallas": 1.0}, seed=0,
                       lookup_batch=ex.batch, table_rows=ex.table_rows,
                       n_tables=ex.n_tables, max_pool=ex.pooling)
    twin = [dataclasses.replace(r, rid=r.rid + n, tenant="xla")
            for r in base]
    tel = eng.run(base + twin)
    assert sum(ev.errors for ev in tel.steps) == 0
    recs = {r.rid: r for r in tel.requests}
    for r in base:
        got = np.asarray(recs[r.rid].logits)
        assert got.shape == (ex.batch,) and np.isfinite(got).all()
        np.testing.assert_allclose(got, recs[r.rid + n].logits,
                                   rtol=1e-5, atol=1e-5)
    assert "logits" not in recs[0].to_dict()

    eng.reset_state()
    tel = eng.run(base, inject=[FaultInjection(step=1, victim="bottom",
                                               seed=0)])
    (inj,) = tel.summary()["faults"]["injections"]
    assert inj["detected"]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    r = subprocess.run([sys.executable, str(root / "chip_smoke.py")],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       cwd=root, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout
