"""Host spans and device-to-host read counts of the serving engine.

``repro.obs.span`` times a block on the profiler's host clock and, given
a tracer, in the tracer; ``ServingEngine.run`` opens ``serving/*`` spans
around its work and counts each blocking read on the step's event."""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import reduce_cfg
from repro.configs.dlrm import DlrmExtras
from repro.configs.registry import get_arch
from repro.core.policy import op_kinds
from repro.obs import Observability, Span, Tracer, span
from repro.serving import FaultInjection, ServingEngine, TenantSpec
from repro.serving import engine as engine_mod
from repro.serving.telemetry import TELEMETRY_SCHEMA_VERSION
from repro.serving.workload import Request

EX = DlrmExtras(n_dense=13, bottom_mlp=(32, 16), n_tables=3,
                table_rows=1024, emb_dim=16, pooling=8, top_mlp=(32, 1),
                batch=4)
#: the op kinds the program registers (``repro.protect.ops``); a test that
#: registers its own adds to them for the rest of its process
BUILTIN_KINDS = ("qgemm", "float_gemm", "embedding_bag", "kv_cache",
                 "kv_cache_paged")
STEP_SPANS = ("serving/upload", "serving/dispatch", "serving/wait",
              "serving/readback")


def dlrm_fetches() -> int:
    """What a DLRM step reads back: one transfer of its logits and its
    packed FaultReport counters."""
    return 1


def n_counters() -> int:
    """The packed counters: each registered op kind's checks and errors,
    retries and corrections."""
    return 2 * len(op_kinds()) + 2


def request(i: int) -> Request:
    rng = np.random.default_rng(i)
    return Request(rid=i, tenant="t", arrival_s=0.0, kind="dlrm",
                   prompt_len=0, max_new_tokens=0, payload={
                       "dense": rng.standard_normal(
                           (EX.batch, EX.n_dense)).astype(np.float32),
                       "bags": rng.integers(
                           0, EX.table_rows,
                           (EX.n_tables, EX.batch, EX.pooling)).astype(
                               np.int32)})


@pytest.fixture(scope="module")
def engine():
    eng = ServingEngine(get_arch("dlrm"), [TenantSpec("t")],
                        dlrm_extras=EX, seed=0)
    eng.warmup(request(0))
    return eng


def host_events(log_dir: str) -> list:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]


def test_span_records_into_a_tracer_on_its_clock():
    ticks = iter([100.0, 100.5, 101.25, 102.0, 102.5])
    t = Tracer(clock=lambda: next(ticks))           # epoch 100.0
    with span("serving/wait", t, lane="l0") as got:
        assert got is t
    with t.span("build", cat="campaign"):
        pass
    assert t.spans == [
        Span("serving/wait", "host", 0.5, 0.75, {"lane": "l0"}),
        Span("build", "campaign", 2.0, 0.5, {})]
    with span("serving/run") as got:                 # no tracer: no record
        assert got is None
    assert len(t) == 2


def test_span_and_tracer_spans_are_profiler_host_events(tmp_path):
    t = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    with span("serving/probe"):
        with t.span("phase/probe"):
            pass
    jax.profiler.stop_trace()
    names = host_events(str(tmp_path))
    assert names.count("serving/probe") == 1
    assert names.count("phase/probe") == 1
    assert [s.name for s in t.spans] == ["phase/probe"]


def test_a_dlrm_step_counts_each_device_to_host_read(engine, monkeypatch):
    """The count on the step event equals the reads seen by wrapping
    every conversion of a device array to the host, and each
    ``jax.device_get`` (one read of all its arrays)."""
    reads = []

    class CountingNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *a, **kw):
            if isinstance(x, jax.Array):
                reads.append(x.shape)
            return np.asarray(x, *a, **kw)

    monkeypatch.setattr(engine_mod, "np", CountingNumpy())
    array_type = type(jax.numpy.zeros(1))
    for name in ("__int__", "__float__", "__index__", "__bool__", "item",
                 "tolist"):
        orig = getattr(array_type, name)

        def counted(self, *a, _orig=orig, **kw):
            reads.append(self.shape)
            return _orig(self, *a, **kw)
        monkeypatch.setattr(array_type, name, counted)
    device_get = jax.device_get

    def counted_get(tree):
        reads.append(tuple(x.shape for x in jax.tree.leaves(tree)))
        return device_get(tree)
    monkeypatch.setattr(jax, "device_get", counted_get)

    tel = engine.run([request(1)], warmup=False)
    (ev,) = tel.steps
    extra = len(op_kinds()) - len(BUILTIN_KINDS)
    assert set(BUILTIN_KINDS) <= set(op_kinds())
    assert ev.host_fetches == len(reads) == dlrm_fetches() == 1
    # the logits, then every counter, in one transfer
    assert reads[0] == ((EX.batch,), (n_counters(),))
    assert n_counters() == 12 + 2 * extra
    assert tel.summary()["host_fetches"] == ev.host_fetches
    d = tel.to_dict()
    assert d["schema_version"] == TELEMETRY_SCHEMA_VERSION == 3
    assert d["steps"][0]["host_fetches"] == ev.host_fetches


def test_engine_spans_share_the_tracer_clock(engine):
    obs = Observability.create()
    tel = engine.run([request(2), request(3)], warmup=False, obs=obs)
    host = [s for s in obs.tracer.spans if s.cat == "host"]
    names = [s.name for s in host]
    assert names.count("serving/run") == 1
    for name in STEP_SPANS:
        assert names.count(name) == 2
    (run,) = [s for s in host if s.name == "serving/run"]
    steps = [s for s in obs.tracer.spans if s.cat == "serving"]
    dispatch = [s for s in host if s.name == "serving/dispatch"]
    assert len(steps) == len(tel.steps) == 2
    for step, ev, d in zip(steps, tel.steps, dispatch):
        # the step span starts when its step was dispatched, on the
        # tracer's clock, and keeps the engine's virtual time as an arg
        assert run.start_s <= step.start_s <= d.start_s
        assert d.start_s - step.start_s < 1e-3
        assert step.dur_s == pytest.approx(ev.duration_s)
        assert step.start_s + step.dur_s <= run.start_s + run.dur_s
        assert step.args["clock_s"] == pytest.approx(
            ev.t_s - ev.duration_s)
    fetches = obs.registry.counter("repro_host_fetches_total")
    assert fetches.value(kind="dlrm", source="serving.engine") == \
        tel.summary()["host_fetches"] == 2 * dlrm_fetches()


@pytest.fixture(scope="module")
def llm_engine():
    eng = ServingEngine(reduce_cfg(get_arch("llama3.2-1b")),
                        [TenantSpec("t")], n_slots=1, max_prompt=4,
                        max_new_tokens=2, seed=0)
    eng.warmup()
    return eng


@pytest.mark.parametrize("case", ["dlrm", "dlrm_flip", "llm_decode"])
def test_the_packed_read_gives_each_counter_as_read_one_by_one(
        case, engine, llm_engine, monkeypatch):
    """The counters and error total one transfer of the packed vector
    gives equal, key for key, what ``_counters_of`` reads scalar by
    scalar from the same step's metrics."""
    eng = llm_engine if case == "llm_decode" else engine
    pairs = []
    readback = eng._readback

    def both(metrics, packed, *outs):
        host, (counters, errors) = readback(metrics, packed, *outs)
        pairs.append(((dict(counters), errors),
                      engine_mod._counters_of(metrics)))
        return host, (counters, errors)
    monkeypatch.setattr(eng, "_readback", both)

    if case == "llm_decode":
        eng.reset_state()
        tel = eng.run([Request(rid=0, tenant="t", arrival_s=0.0,
                               prompt_len=4, max_new_tokens=2, seed=5)],
                      warmup=False)
        assert [ev.kind for ev in tel.steps] == ["prefill", "decode"]
    else:
        inject = ([FaultInjection(step=eng.global_step, victim="bottom")]
                  if case == "dlrm_flip" else [])
        tel = eng.run([request(4)], warmup=False, inject=inject)
    assert len(pairs) == len(tel.steps)
    for (got, errors), (want, want_errors) in pairs:
        assert got == want and errors == want_errors
        assert set(got) == {f"{k}_{c}" for k in op_kinds()
                            for c in ("checks", "errors")} | {
                                "retries", "corrections"}
        assert got["qgemm_checks"] > 0
    ev = tel.steps[-1]
    assert ev.errors == pairs[-1][0][1]
    assert (ev.errors > 0) == (case == "dlrm_flip")
