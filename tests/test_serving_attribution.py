"""Per-request detection attribution + the serving obs export.

A mid-stream injection must blame exactly the requests resident in the
affected lane's slots when the flag fired — nobody in a clean pass, and
never requests that had already retired or not yet admitted.  The obs
export of a soak cell must agree with the artifact: every detected
injected fault has a detection FaultEvent with an op kind, a step, and
at least one attributed request id, and the Prometheus counters match
the cell's SoakMetrics numbers."""
import json

import pytest

from repro.configs import reduce_cfg
from repro.configs.registry import get_arch
from repro.obs import Observability, validate_event
from repro.protect import ProtectionPlan
from repro.serving import (FaultInjection, ServingEngine, TenantSpec,
                           chat_stream)

N_SLOTS = 2
MAX_PROMPT = 8
MAX_NEW = 4


@pytest.fixture(scope="module")
def engine():
    cfg = reduce_cfg(get_arch("llama3.2-1b"))
    tenants = [TenantSpec("t", ProtectionPlan.parse("*:policy=log",
                                                    name="t"))]
    eng = ServingEngine(cfg, tenants, n_slots=N_SLOTS,
                        max_prompt=MAX_PROMPT, max_new_tokens=MAX_NEW,
                        seed=0)
    eng.warmup()
    return eng


def _stream(n, seed=0):
    return chat_stream(n, tenants={"t": 1.0}, rate_rps=500.0, seed=seed,
                       mean_prompt=6, max_prompt=MAX_PROMPT,
                       mean_output=3, max_output=MAX_NEW)


def test_clean_run_has_no_suspects(engine):
    engine.reset_state()
    tel = engine.run(_stream(6, seed=1))
    s = tel.summary()
    assert s["faults"]["suspect_requests"] == 0
    assert all(r.detections == 0 and not r.suspect for r in tel.requests)
    assert s["per_tenant"]["t"]["suspect"] == 0
    assert s["per_tenant"]["t"]["detections"] == 0


def test_injection_attributes_to_resident_requests_exactly(engine):
    engine.reset_state()
    tel = engine.run(_stream(8, seed=3),
                     inject=[FaultInjection(step=2, victim="mlp.down",
                                            seed=0)])
    s = tel.summary()
    flagged = [ev for ev in tel.steps if ev.errors > 0]
    assert flagged
    resident = set()
    for ev in flagged:
        assert ev.slot_rids, "flagged step lost its slot occupancy"
        resident |= set(ev.slot_rids)
    by_rid = {r.rid: r for r in tel.requests}
    # exactly the resident requests are suspect — nobody else
    for rid, rec in by_rid.items():
        assert rec.suspect == (rid in resident), rid
        assert rec.detections == sum(
            1 for ev in flagged if rid in ev.slot_rids)
    assert s["faults"]["suspect_requests"] == len(resident)
    assert s["per_tenant"]["t"]["suspect"] == len(resident)
    # the injection record blames the first flagged step's residents
    (inj,) = s["faults"]["injections"]
    assert inj["detected"]
    assert tuple(inj["attributed_rids"]) == flagged[0].slot_rids
    assert len(inj["attributed_rids"]) >= 1


def test_attribution_is_idempotent(engine):
    engine.reset_state()
    tel = engine.run(_stream(8, seed=3),
                     inject=[FaultInjection(step=2, victim="mlp.down",
                                            seed=0)])
    tel.attribute_detections()
    first = {r.rid: r.detections for r in tel.requests}
    tel.summary()                      # finalize runs attribution again
    tel.attribute_detections()
    assert {r.rid: r.detections for r in tel.requests} == first


def test_engine_obs_detection_events_carry_rids(engine):
    engine.reset_state()
    obs = Observability.create()
    tel = engine.run(_stream(8, seed=3),
                     inject=[FaultInjection(step=2, victim="mlp.down",
                                            seed=0)],
                     obs=obs)
    detections = [e for e in obs.bus if e.kind == "detection"]
    injections = [e for e in obs.bus if e.kind == "injection"]
    assert detections and injections
    assert "mlp.down" in injections[0].op
    # one global step can hold several step events (a prefill and a
    # decode); a detection blames the residents of the event that flagged
    flagged = {}
    for ev in tel.steps:
        if ev.errors > 0:
            flagged.setdefault(ev.step, []).append(ev.slot_rids)
    for e in detections:
        assert e.op and e.step in flagged
        assert e.request_ids in flagged[e.step]
        assert len(e.request_ids) >= 1
    # per-op error counters in the registry match the timeline totals
    totals = tel.fault_counters()
    errs = obs.registry.counter("repro_abft_errors_total")
    for op in {e.op for e in detections}:
        assert errs.value(op=op, source="serving.engine") == \
            totals[f"{op}_errors"]
    # spans and step counters cover every telemetry step
    steps = obs.registry.counter("repro_steps_total")
    assert steps.total() == len(tel.steps)
    assert len([s for s in obs.tracer.spans
                if s.cat == "serving"]) == len(tel.steps)
    # obs must not leak into the next (clean) run
    engine.reset_state()
    engine.run(_stream(4, seed=4))
    assert steps.total() == len(tel.steps)


@pytest.fixture(scope="module")
def soak_cell_with_obs():
    from repro.serving.soak import SoakSpec, run_soak_cell, soak_plans

    spec = SoakSpec(name="serving_soak", arch="llama3.2-1b",
                    arrivals=("poisson",), n_requests=16, n_slots=2,
                    rate_rps=300.0, max_new_tokens=8, seed=0)
    (plan,) = soak_plans(spec)
    obs = Observability.create()
    cell = run_soak_cell(plan, obs=obs)
    return plan, cell, obs


def test_soak_cell_obs_counters_match_metrics(soak_cell_with_obs):
    plan, cell, obs = soak_cell_with_obs
    m = cell["metrics"]
    reg = obs.registry
    pairs = [("repro_injections_total", m["samples"]),
             ("repro_detections_total", m["detected"]),
             ("repro_escapes_total", m["escapes"]),
             ("repro_false_positives_total", m["false_positives"])]
    for name, want in pairs:
        assert reg.counter(name).value(cell=plan.cell_id) == want, name
    prom = reg.to_prometheus()
    assert f'repro_detections_total{{cell="{plan.cell_id}"}} ' \
        f'{m["detected"]}' in prom


def test_soak_cell_obs_every_detected_fault_has_attributed_event(
        soak_cell_with_obs, tmp_path):
    plan, cell, obs = soak_cell_with_obs
    m = cell["metrics"]
    assert m["detected"] >= 1, "soak cell did not detect its injection"
    detections = [e for e in obs.bus if e.kind == "detection"]
    inj_events = [e for e in obs.bus if e.kind == "injection"
                  and e.source == "serving.soak"]
    assert len(inj_events) == m["samples"]
    for inj in m["injections"]:
        if not inj["detected"]:
            continue
        hits = [e for e in detections
                if e.step == inj["detect_step"] and e.request_ids]
        assert hits, inj
        assert all(e.op for e in hits)
        assert set(inj["attributed_rids"]) <= {
            r for e in hits for r in e.request_ids}
    # the cell-summary event carries the detection rate as detector_value
    (cell_ev,) = [e for e in obs.bus if e.kind == "cell"]
    assert cell_ev.cell_id == plan.cell_id
    assert cell_ev.detector_value == pytest.approx(m["detection_rate"])
    # the JSONL export validates line by line
    paths = obs.write(str(tmp_path))
    for line in open(paths["events"]):
        validate_event(json.loads(line))
