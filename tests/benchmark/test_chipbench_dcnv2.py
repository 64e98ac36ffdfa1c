"""The DLRMv2 cell: its stream, work counts, files, and a tiny CPU run of
its runner through the harness."""
from __future__ import annotations

import dataclasses
import json

import jax
import numpy as np
import pytest

import chipbench_tiny as tiny

SPEC = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
CFG = json.loads((tiny.BENCH / "configs" / "dlrm-dcnv2.json").read_text())
TRAFFIC = json.loads((tiny.BENCH / "traffic" / "multihot512.json")
                     .read_text())
WORKLOAD = "dlrm-dcnv2.multihot512"

#: a tiny DLRMv2: three tables of unequal rows and pools, one smaller than
#: a row group, one pool past the kernel's DMA ring
TINY = dict(CFG, name="tiny-dcn", source="test", n_dense=13,
            bottom_mlp=[32, 16], n_tables=3, table_rows=[5, 300, 2003],
            emb_dim=16, pooling=[1, 7, 40], top_mlp=[32, 1],
            cross_layers=2, cross_rank=8, batch=4, reduced=[])
TINY_TRAFFIC = dict(TRAFFIC, batch="config")
#: the real cell's limits, with the tiny cell's logit gap (CPU, XLA paths:
#: 0.041-0.090 over seeds 1-6; the int4 control 0.65-1.05)
TINY_LIMITS = dict(tiny.LIMITS, logit_gap={"limit": 0.2, "pass_if": "<="})


def runner_module():
    import harness
    return harness.load_module(
        tiny.BENCH / "runners" / "dlrm_dcnv2_serving.py", "runner_dcnv2")


def write_cell(root):
    """The tiny DLRMv2 cell's files under ``root``; -> its BENCHMARK.json."""
    for d in ("configs", "traffic", "limits"):
        (root / d).mkdir(parents=True, exist_ok=True)
    (root / "configs" / "tiny-dcn.json").write_text(json.dumps(TINY))
    (root / "traffic" / "multihot.json").write_text(json.dumps(TINY_TRAFFIC))
    (root / "limits" / "tiny-dcn.multihot.json").write_text(
        json.dumps(TINY_LIMITS))
    tiny.write_cell(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny-dcn", "source": "test",
                        "file": "configs/tiny-dcn.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "tiny-dcn.multihot", "config": "tiny-dcn",
                          "traffic": "multihot", "chips": 1, "why": "test"}]
    for m in spec["per_layer"] + spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-dcn.multihot"]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


def test_stream_sizes_and_determinism():
    mod = runner_module()
    s = mod.stream(TRAFFIC, CFG, 2 ** 31 + 11)
    assert s.batch == 512 and sum(s.pools) == 214
    p = s.payload(3)
    assert p["dense"].shape == (512, 13) and p["dense"].dtype == np.float32
    assert p["bags"].shape == (512, 214) and p["bags"].dtype == np.int32
    # each table's columns lie in its own rows (the chip's half of 40M)
    high = np.repeat(CFG["table_rows"], CFG["pooling"])
    assert (p["bags"] >= 0).all() and (p["bags"] < high).all()
    assert high.max() == 20_000_000 and p["bags"].max() > 10_000_000
    again = mod.stream(TRAFFIC, CFG, 2 ** 31 + 11).payload(3)
    assert all((p[k] == again[k]).all() for k in p)
    other = [s.payload(4), mod.stream(TRAFFIC, CFG, 7).payload(3)]
    assert all((o["bags"] != p["bags"]).any() for o in other)
    assert s.due_s(5) is None                           # a closed loop


def test_stream_refuses_other_laws():
    mod = runner_module()
    with pytest.raises(ValueError):
        mod.stream(dict(TRAFFIC, pooling={"law": "fixed", "value": 1}), CFG,
                   1)


def test_work_counts_by_hand():
    import work_dcnv2 as w
    assert w.eb_slots(CFG, 1) == 214
    assert w.int8_ops(CFG, 512) == 16_415_195_136
    shapes = w.gemm_shapes(CFG, 512)
    assert len(shapes) == 3 + 6 + 5
    assert shapes[3] == (512, 3456, 512) and shapes[4] == (512, 512, 3456)
    assert shapes[9] == (512, 3456, 1024)
    # Σ_t B·pool_t·(d + 8) + 26·B·(4d + 4)
    assert w.eb_bytes(CFG, 512) == 512 * 214 * 136 + 26 * 512 * 516


def test_config_traffic_and_cell_are_found():
    import harness
    cell, cfg = harness.find_cell(SPEC, WORKLOAD)
    assert cell["chips"] == 1 and cfg["name"] == "dlrm-dcnv2"
    assert harness.find(tiny.BENCH, "traffic", cell["traffic"], ".json")
    assert harness.find(tiny.BENCH, "runners", CFG["runner"], ".py")
    assert harness.find(tiny.BENCH, "limits", WORKLOAD, ".json")
    # one chip's share: the five 40M-row tables cut to 20M, nothing else
    cut = [a != b for a, b in zip(CFG["table_rows"],
                                  CFG["published"]["table_rows"])]
    assert sum(cut) == 5 and max(CFG["table_rows"]) == 20_000_000
    assert sum(CFG["table_rows"]) == 104_184_588


def test_new_metrics_read_only_this_cell():
    names = {"dcn_eb_roofline", "dcn_qgemm_roofline", "dcn_mfu",
             "eb_slots_per_sample"}
    entries = [m for m in SPEC["per_layer"] if m["name"] in names]
    assert len(entries) == len(names)
    assert all(m["workloads"] == [WORKLOAD] for m in entries)
    # the kernels' times are the accepted metrics', read in this cell too
    for m in SPEC["per_layer"]:
        if m["name"] in ("eb_kernel_ms", "qgemm_kernel_ms",
                         "abft_overhead_pct"):
            assert m["workloads"][-1] == WORKLOAD


def test_reference_draws_the_programs_weights():
    """The benchmark's reference, which imports nothing of the program,
    agrees with the program's own plain reference on the weights
    ``init_dlrm`` draws: the recipes match, chunked tables included."""
    from reference import dlrm_dcnv2 as ref

    from repro.models.dlrm import init_dlrm
    from repro.models.dlrm_reference import dlrm_reference
    from repro.sharding import values_of
    mod = runner_module()
    ex = mod.extras(TINY)
    s = mod.stream(TINY_TRAFFIC, TINY, 5)
    p = s.payload(0)
    params = values_of(jax.jit(lambda k: init_dlrm(k, ex))(
        jax.random.key(1234)))
    want = np.asarray(dlrm_reference(params, p["dense"], p["bags"], ex))
    got = ref.logits(1234, TINY, p["dense"][None], p["bags"][None])[0]
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


def test_reference_chunks_a_table():
    """A table past one chunk is drawn chunk by chunk: the benchmark's
    reference reads the rows the program's ``init_embedding_bags`` wrote,
    across a chunk boundary."""
    from reference import dlrm_dcnv2 as ref

    from repro.layers import embedding
    from repro.sharding import values_of
    rows, d = (3, 70_000), 8
    cfg = {"table_rows": list(rows), "emb_dim": d}
    assert ref.chunk_rows(cfg) == embedding.init_chunk(rows) == 1 << 16
    tables = values_of(jax.jit(lambda k: embedding.init_embedding_bags(
        k, rows, d))(jax.random.key(9)))
    offs = embedding.table_offsets(rows)
    ids = np.asarray([0, 65535, 65536, 69999])
    tkey = jax.random.split(jax.random.key(9), 2)[1]    # table 1's key
    got = ref._rows(tkey, ids, 1 << 16, d)
    for name, v in zip(("table", "alphas", "betas"), got):
        np.testing.assert_array_equal(v, np.asarray(tables[name])[
            offs[1] + ids])


@pytest.fixture(scope="module")
def tiny_runs(tmp_path_factory):
    import harness
    root = tmp_path_factory.mktemp("dcn")
    spec = write_cell(root)

    def run(seed, trace=False, **kw):
        return harness.run("tiny-dcn.multihot", seed, 0.3, trace,
                           spec_path=spec, root=root,
                           peaks_path=root / "peaks.json", require_tpu=False,
                           log=lambda msg: None, **kw)
    return run


def test_tiny_cell_runs_through_the_harness(tiny_runs):
    res = tiny_runs(3, control_bits=4)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["injected_flip_flags"]["value"] >= 1
    assert res["control"]["correct"] is False
    assert set(res["metrics"]) == {m["name"] for m in SPEC["end_to_end"]}


def test_tiny_cell_traced_reads_the_slot_counter(tiny_runs):
    res = tiny_runs(4, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    # 1 + 7 + 40 slots a sample, as the kernel ran them
    assert m["eb_slots_per_sample"]["value"] == 48.0
    assert m["dcn_mfu"]["value"] > 0
    # 3 tables checked, 2 bottom + 2 x 2 cross + 2 top GEMMs
    assert m["abft_checks_per_request"]["value"] == 3 + 8


def test_tiny_dcn_config_is_the_extras_it_builds():
    ex = runner_module().extras(TINY)
    assert ex.ragged and ex.interaction == "dcnv2"
    assert dataclasses.asdict(ex)["pooling"] == (1, 7, 40)
    assert ex.inter_dim == 4 * 16
