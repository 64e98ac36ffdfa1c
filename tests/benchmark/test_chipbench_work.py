"""Operation and byte counts of the chip benchmark's cells, against hand
counts, and the peaks table."""
from __future__ import annotations

import json

import pytest

import chipbench_tiny as tiny
import work

TABLE1 = json.loads((tiny.BENCH / "configs" / "dlrm-table1.json").read_text())
SYNTH64 = json.loads((tiny.BENCH / "configs" / "dlrm-synth64.json")
                     .read_text())


def test_gemm_shapes_of_table1():
    assert work.gemm_shapes(TABLE1, 10) == [
        (10, 13, 512), (10, 512, 256), (10, 256, 128),
        (10, 479, 1024), (10, 1024, 1024), (10, 1024, 512), (10, 512, 256),
        (10, 256, 1)]


@pytest.mark.parametrize("cfg,batch,int8,bf16", [
    # 2 x (13*512 + 512*256 + 256*128 + 479*1024 + 1024*1024 + 1024*512
    #      + 512*256 + 256) = 4,730,368 per sample; 351 pairs x 2 x 128
    (TABLE1, 10, 47_303_680, 898_560),
    (TABLE1, 20, 94_607_360, 1_797_120),
    # 2 x (512*512 + 512*64 + 100*1024 + 2*1024*1024 + 1024) = 4,990,976;
    # 36 pairs x 2 x 64
    (SYNTH64, 128, 638_844_928, 589_824),
])
def test_model_ops(cfg, batch, int8, bf16):
    assert work.model_ops(cfg, batch) == {"int8": int8, "bf16": bf16}


def test_qgemm_work_by_hand():
    # 2 m k (n+1); A m k + B' k (n+1) + C 4 m n + flags 4 m
    assert work.qgemm_work(10, 13, 512) == (133_380,
                                            130 + 6_669 + 20_480 + 40)
    assert work.qgemm_work(20, 1024, 1) == (81_920,
                                            20_480 + 2_048 + 80 + 80)


@pytest.mark.parametrize("cfg,batch,pool,nbytes", [
    # 26,000 slots x (128 + 8) + 260 bags x (4 x 128 + 4)
    (TABLE1, 10, 100, 3_670_160),
    # 102,400 x (64 + 8) + 1,024 x (4 x 64 + 4)
    (SYNTH64, 128, 100, 7_639_040),
    # one-hot: 520 x 136 + 520 x 516
    (TABLE1, 20, 1, 339_040),
])
def test_eb_bytes(cfg, batch, pool, nbytes):
    assert work.eb_bytes(cfg, batch, pool) == nbytes


def test_v5e_peaks_and_an_unknown_device_kind():
    peaks = work.load_peaks(tiny.BENCH / "peaks.json", "TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["int8_ops_per_s"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks(tiny.BENCH / "peaks.json", "TPU v4")
    with pytest.raises(KeyError):
        work.load_peaks(tiny.BENCH / "peaks.json", "cpu")


def test_least_seconds_takes_the_binding_roof():
    peaks = {"hbm_bytes_per_s": 1e9}
    assert work.least_seconds(4e9, 1e6, 1e12, peaks) == pytest.approx(4e-3)
    assert work.least_seconds(1e6, 5e6, 1e12, peaks) == pytest.approx(5e-3)


class _Ctx:
    def __init__(self, cfg, batch, n, window_s):
        import harness
        import trace_reduce

        class S:
            pass
        self.cfg, self.stream = cfg, S()
        self.stream.batch = batch
        self.window = [{"aborted": False, "latency_s": 0.02, "step_s": 0.015,
                        "checks": 34} for _ in range(n)]
        self.window_s = window_s
        self.peaks = work.load_peaks(tiny.BENCH / "peaks.json",
                                     "TPU v5 lite")
        self.work, self.reduce = work, trace_reduce
        self.harness = harness


def _reader(name):
    import harness
    return harness.load_module(tiny.BENCH / "metrics" / f"{name}.py",
                               f"test_{name}")


def test_mfu_of_a_table1_window():
    # 500 requests of 10 samples in 10 s: 500 samples/s
    ctx = _Ctx(TABLE1, 10, 500, 10.0)
    want = 100 * 50 * (47_303_680 / 393e12 + 898_560 / 197e12)
    assert _reader("mfu").read(ctx) == pytest.approx(want)


def test_host_time_and_checks_per_request():
    ctx = _Ctx(TABLE1, 10, 4, 1.0)
    assert _reader("host_ms_per_request").read(ctx) == pytest.approx(5.0)
    assert _reader("abft_checks_per_request").read(ctx) == 34
