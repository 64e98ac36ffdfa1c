"""The serving engine's host spans (``serving/*``) in a profiler trace,
read with the benchmark's trace reduction: from the CPU's profiler, on a
hand-made trace and on a recorded v5e trace.

The reduction's loader keeps host spans by name prefix; these tests widen
it to the engine's prefix as well (``ENGINE_PREFIXES``)."""
from __future__ import annotations

import pytest

import chipbench_tiny as tiny
import trace_reduce as tr

ENGINE_PREFIXES = ("bench/", "serving/")
#: the engine's spans of one DLRM request, in the order they open
STEP = ("serving/upload", "serving/dispatch", "serving/wait",
        "serving/readback")
RECORDED = (tiny.REPO / "tests" / "benchmark" / "data"
            / "dlrm_table1_pool100_spans.json.gz")


def inside(spans: list, name: str, lo: float, hi: float) -> list:
    return [s for s in spans if s[2] == name and lo <= s[0] <= s[1] <= hi]


def per_request_ms(trace: dict, name: str, lo: float, hi: float,
                   n: int) -> float:
    """Σ durations of the spans called ``name`` inside [lo, hi), ÷ n."""
    return sum(e - s for s, e, _ in inside(trace["spans"], name, lo, hi)
               ) / 1e6 / n


def idle_by_span(trace: dict, lo: float, hi: float) -> dict:
    ops = trace["ops"][sorted(trace["ops"])[0]]
    return dict(tr.idle_gaps(trace, ops, lo, hi, n=100,
                             exclude={"bench/lane/protected"}))


def test_engine_spans_nest_once_per_request(tmp_path, monkeypatch):
    """Tiny DLRM requests served under the CPU's profiler, each inside a
    ``bench/engine_run`` annotation as the harness serves them."""
    import jax
    import numpy as np

    from repro.configs.dlrm import DlrmExtras
    from repro.configs.registry import get_arch
    from repro.serving import ServingEngine, TenantSpec
    from repro.serving.workload import Request

    cfg = tiny.CONFIG
    ex = DlrmExtras(n_dense=cfg["n_dense"],
                    bottom_mlp=tuple(cfg["bottom_mlp"]),
                    n_tables=cfg["n_tables"], table_rows=cfg["table_rows"],
                    emb_dim=cfg["emb_dim"], pooling=cfg["pooling"],
                    top_mlp=tuple(cfg["top_mlp"]), batch=cfg["batch"])
    rng = np.random.default_rng(0)

    def request(i):
        return Request(rid=i, tenant="t", arrival_s=0.0, kind="dlrm",
                       prompt_len=0, max_new_tokens=0, payload={
                           "dense": rng.standard_normal(
                               (ex.batch, ex.n_dense)).astype(np.float32),
                           "bags": rng.integers(
                               0, ex.table_rows,
                               (ex.n_tables, ex.batch, ex.pooling)
                           ).astype(np.int32)})

    eng = ServingEngine(get_arch("dlrm"), [TenantSpec("t")],
                        dlrm_extras=ex, seed=0)
    eng.warmup(request(0))
    n = 3
    jax.profiler.start_trace(str(tmp_path))
    for i in range(n):
        with jax.profiler.TraceAnnotation("bench/engine_run"):
            eng.run([request(i)], warmup=False)
    jax.profiler.stop_trace()

    monkeypatch.setattr(tr, "SPAN_PREFIX", ENGINE_PREFIXES)
    got = tr.load(tr.find_xspace(str(tmp_path)))
    spans = got["spans"]
    outer = [s for s in spans if s[2] == "bench/engine_run"]
    assert len(outer) == n
    assert len(spans) == n * (2 + len(STEP))
    for lo, hi, _ in outer:
        (run,) = inside(spans, "serving/run", lo, hi)
        step = [inside(spans, name, run[0], run[1]) for name in STEP]
        assert all(len(s) == 1 for s in step)
        ends = [t for (s,) in step for t in s[:2]]
        assert ends == sorted(ends)        # in order, none overlapping
    # the span metrics' reduction reads a number from the CPU's profile
    lo, hi = outer[0][0], outer[-1][1]
    for name in ("serving/run",) + STEP:
        assert per_request_ms(got, name, lo, hi, n) > 0


#: one device plane, two requests; times in ns.  Request 1 [0, 100):
#: upload [10, 20), dispatch [20, 30), op [25, 60) under wait [30, 60),
#: readback [60, 80).  Request 2 [100, 200): upload [104, 110), dispatch
#: [110, 115), op [115, 150) under wait [115, 150), readback [150, 190).
HAND = {
    "ops": {"/device:TPU:0": [[25, 60, "jit_forward", ""],
                              [115, 150, "jit_forward", ""]]},
    "modules": {},
    "spans": [[0, 200, "bench/lane/protected"],
              [0, 100, "bench/engine_run"], [5, 95, "serving/run"],
              [10, 20, "serving/upload"], [20, 30, "serving/dispatch"],
              [30, 60, "serving/wait"], [60, 80, "serving/readback"],
              [100, 200, "bench/engine_run"], [102, 198, "serving/run"],
              [104, 110, "serving/upload"], [110, 115, "serving/dispatch"],
              [115, 150, "serving/wait"], [150, 190, "serving/readback"]],
}


def test_idle_gaps_fall_to_the_innermost_engine_span():
    # idle [0, 25): engine_run 5, run 5, upload 10, dispatch 5;
    # [60, 115): readback 20, run 15 + 2, engine_run 5 + 2,
    # upload 6, dispatch 5; [150, 200): readback 40, run 8, engine_run 2
    got = idle_by_span(HAND, 0, 200)
    assert got == {"serving/readback": pytest.approx(60e-9),
                   "serving/run": pytest.approx(30e-9),
                   "serving/upload": pytest.approx(16e-9),
                   "bench/engine_run": pytest.approx(14e-9),
                   "serving/dispatch": pytest.approx(10e-9)}
    assert sum(got.values()) == pytest.approx(
        (200 - tr.busy_ns(HAND["ops"]["/device:TPU:0"], 0, 200)) * 1e-9)
    assert per_request_ms(HAND, "serving/readback", 0, 200, 2) == \
        pytest.approx(30e-6)
    assert per_request_ms(HAND, "serving/upload", 0, 200, 2) == \
        pytest.approx(8e-6)
    assert per_request_ms(HAND, "serving/dispatch", 0, 200, 2) == \
        pytest.approx(7.5e-6)


def test_a_recorded_chip_trace_with_engine_spans():
    """Five Table-I requests (batch 10, pooling 100) on a TPU v5e, the
    engine's spans kept; ``records`` holds each request's engine step
    (``step_s``) and ``host_fetches``."""
    rec = tr.from_json(str(RECORDED))
    lo, hi = tr.span_range(rec, "bench/lane/protected")
    n = 5
    for name in ("bench/engine_run", "serving/run") + STEP:
        assert len(inside(rec["spans"], name, lo, hi)) == n
    ms = {name: per_request_ms(rec, name, lo, hi, n)
          for name in ("bench/engine_run", "serving/run") + STEP}
    assert {name: round(v * n * 1e6) for name, v in ms.items()} == {
        "bench/engine_run": 76_498_438, "serving/run": 76_325_190,
        "serving/upload": 3_481_519, "serving/dispatch": 5_898_041,
        "serving/wait": 38_331_088, "serving/readback": 27_342_700}
    # the engine's spans account for the call and for its timed step
    assert ms["serving/run"] >= 0.95 * ms["bench/engine_run"]
    step_ms = 1e3 * sum(r["step_s"] for r in rec["records"]) / n
    assert ms["serving/dispatch"] + ms["serving/wait"] == pytest.approx(
        step_ms, rel=0.05)
    assert {r["host_fetches"] for r in rec["records"]} == {13}
    # every idle nanosecond is named, and the engine's spans name nearly
    # all of it: the harness's own span keeps under 5%
    idle = idle_by_span(rec, lo, hi)
    ops = rec["ops"]["/device:TPU:0"]
    assert sum(idle.values()) == pytest.approx(
        (hi - lo - tr.busy_ns(ops, lo, hi)) / 1e9)
    assert idle["bench/engine_run"] < 0.05 * sum(idle.values())
    assert max(idle, key=idle.get) == "serving/readback"
