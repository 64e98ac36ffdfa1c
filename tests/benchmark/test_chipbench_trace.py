"""The trace reduction: busy union, idle share, kernel time by jit name,
idle gaps by host span; on hand-made traces and on a recorded one."""
from __future__ import annotations

import pytest

import chipbench_tiny as tiny
import trace_reduce as tr

EB = ("(f32[26,16,128]{2,1,0:T(8,128)S(1)}) custom-call(s32[26,10,100] "
      "%get-tuple-element.351)")
QG = "(s32[128,1152]{1,0:T(8,128)S(1)}) custom-call(s8[128,1024] %x)"
#: reads the EB kernel's output: its text names the kernel, its name not
USE = "f32[26,10,128]{2,1,0} fusion(%vmap_jit_abft_eb_pallas__.1)"

#: one device plane, two requests.  Times in ns:
#:   request 1 [0, 100): eb [10, 40), qgemm [40, 50), fusion [45, 60)
#:   request 2 [100, 200): eb [120, 150), qgemm [150, 155)
HAND = {
    "ops": {"/device:TPU:0": [
        [10, 40, "vmap_jit_abft_eb_pallas__.1", EB],
        [40, 50, "abft_qgemm_pallas.9", QG],
        [45, 60, "fusion.3", USE],
        [120, 150, "vmap_jit_abft_eb_pallas__.1", EB],
        [150, 155, "abft_qgemm_pallas.9", QG],
    ]},
    "modules": {"/device:TPU:0": [[10, 60, "jit_forward(1)"],
                                  [120, 155, "jit_forward(1)"]]},
    "spans": [[0, 200, "bench/lane/protected"],
              [0, 100, "bench/request"], [0, 8, "bench/build_request"],
              [8, 100, "bench/engine_run"],
              [100, 200, "bench/request"], [100, 115, "bench/build_request"],
              [115, 200, "bench/engine_run"]],
}


def ops():
    return HAND["ops"]["/device:TPU:0"]


def test_busy_is_the_union_of_op_intervals():
    # [10, 60) and [120, 155): overlapping qgemm/fusion counted once
    assert tr.busy_ns(ops(), 0, 200) == 50 + 35
    assert tr.busy_ns(ops(), 30, 130) == 30 + 10
    assert tr.union([(5, 9), (0, 3), (2, 4)]) == [(0, 4), (5, 9)]


def test_idle_share_of_a_window():
    lo, hi = tr.span_range(HAND, "bench/lane/protected")
    assert (lo, hi) == (0, 200)
    assert 1 - tr.busy_ns(ops(), lo, hi) / (hi - lo) == pytest.approx(0.575)


def test_kernel_time_by_enclosing_jit_name():
    assert tr.op_time_ns(ops(), "abft_eb_pallas", 0, 200) == 60
    assert tr.op_time_ns(ops(), "abft_qgemm_pallas", 0, 200) == 15
    assert tr.module_time_ns(HAND["modules"]["/device:TPU:0"],
                             "jit_forward", 0, 200) == 85
    top = tr.top_ops(ops(), 0, 200)
    assert [round(v * 1e9) for _, v in top] == [60, 15, 15]
    assert top[0][0] == ("vmap_jit_abft_eb_pallas__.1 = (f32[26,16,128]) "
                         "custom-call(s32[26,10,100] %get-tuple-element.351)")
    assert top[2][0].startswith("fusion.3 = f32[26,10,128] fusion(")


def test_split_hlo_names():
    assert tr.split_hlo("%fusion.3 = s32[2] fusion(%a)") == (
        "fusion.3", "s32[2] fusion(%a)")
    assert tr.split_hlo("jit_forward(123)") == ("jit_forward(123)", "")


def test_idle_gaps_are_named_by_the_host_span_they_fall_in():
    # idle: [0, 10) = build 8 + engine 2; [60, 120) = engine 40 + build 15
    # + engine 5; [155, 200) = engine 45.  Each piece goes to the
    # innermost span open then, never to the enclosing request.
    assert tr.gaps(ops(), 0, 200) == [(0, 10), (60, 120), (155, 200)]
    got = tr.idle_gaps(HAND, ops(), 0, 200,
                       exclude={"bench/lane/protected"})
    assert got == [["bench/engine_run", pytest.approx(92e-9)],
                   ["bench/build_request", pytest.approx(23e-9)]]
    assert sum(v for _, v in got) == pytest.approx(
        (200 - tr.busy_ns(ops(), 0, 200)) * 1e-9)
    assert tr.idle_gaps(HAND, ops(), 300, 400) == [
        ["host:other", pytest.approx(100e-9)]]


def test_loader_reads_host_spans_of_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/lane/protected"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench/request"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = tr.load(tr.find_xspace(str(tmp_path)))
    names = [s[2] for s in got["spans"]]
    assert names.count("bench/request") == 2
    lo, hi = tr.span_range(got, "bench/lane/protected")
    assert hi > lo and all(lo <= s[0] <= s[1] <= hi for s in got["spans"])
    assert got["ops"] == {}           # the CPU has no device plane


def test_find_xspace_without_a_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.find_xspace(str(tmp_path))



def test_a_recorded_chip_trace():
    """Five Table-I requests per lane on a TPU v5e, protected lane then
    unprotected (the run's host spans were ``bench/p`` and ``bench/u``)."""
    rec = tr.from_json(str(tiny.REPO / "tests" / "benchmark" / "data"
                           / "dlrm_table1_pool100_trace.json.gz"))
    ops = rec["ops"]["/device:TPU:0"]
    mods = rec["modules"]["/device:TPU:0"]
    lo, hi = tr.span_range(rec, "bench/p")
    assert sum(1 for a, b, name in rec["spans"] if name == "bench/request"
               and lo <= a <= b <= hi) == 5
    busy = tr.busy_ns(ops, lo, hi)
    eb = tr.op_time_ns(ops, "abft_eb_pallas", lo, hi)
    qg = tr.op_time_ns(ops, "abft_qgemm_pallas", lo, hi)
    fwd = tr.module_time_ns(mods, "jit_forward", lo, hi)
    assert (busy, eb, qg, fwd) == (34_272_376, 28_920_582, 245_099,
                                   34_337_589)
    # every op runs inside its program; the kernels inside the busy time
    assert eb + qg < busy <= fwd < hi - lo
    idle = tr.idle_gaps(rec, ops, lo, hi, exclude={"bench/p"})
    assert sum(v for _, v in idle) == pytest.approx((hi - lo - busy) / 1e9)
    assert idle[0][0] == "bench/request"
    # the unprotected lane runs neither kernel, and less device time
    lo_u, hi_u = tr.span_range(rec, "bench/u")
    assert tr.op_time_ns(ops, "abft_eb_pallas", lo_u, hi_u) == 0
    assert tr.op_time_ns(ops, "abft_qgemm_pallas", lo_u, hi_u) == 0
    assert 0 < tr.busy_ns(ops, lo_u, hi_u) < busy / 5
