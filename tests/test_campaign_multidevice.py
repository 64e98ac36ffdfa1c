"""Mesh-sharded campaign execution: the ``CampaignSpec.mesh`` axis,
executor mesh-slice placement + device-count fallback, and the
multidevice grid whose soak cells run ``checked_psum`` through a real
shard_map collective (subprocess — the tier-1 host has one device).
"""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import pytest

from repro.campaign import (CampaignSpec, expand, get_target,
                            latency_markdown, resolve_device_count,
                            run_cell)
from repro.campaign.executor import _cell_mesh
from repro.campaign.grids import multidevice_specs
from repro.campaign.spec import CellPlan, cell_seed


def _plan(target="train_payload_shard", dtype="int8", shards=4, steps=2,
          samples=2):
    cid = f"mdtest/{target}/{dtype}/{shards}"
    return CellPlan(
        cell_id=cid, target=target, fault_model="bitflip",
        bit_band="significant", shape=(2, 8), dtype=dtype,
        samples=samples, clean_samples=1, flips=1,
        seed=cell_seed(0, cid), measure_overhead=False, steps=steps,
        data_shards=shards)


# ---------------------------------------------------------------------------
# spec expansion: the mesh axis
# ---------------------------------------------------------------------------

def test_mesh_sweep_gated_on_shardable_targets():
    spec = CampaignSpec(
        name="t", targets=("gemm_packed", "train_payload"),
        bit_bands=("significant",), dtypes=("int8",),
        samples=2, steps=2, mesh=(1, 4))
    plans, skipped = expand(spec)
    by_target = {}
    for p in plans:
        by_target.setdefault(p.target, []).append(p)
    # shardable target: both shard counts, suffix only when sharded
    tp = sorted(p.data_shards for p in by_target["train_payload"])
    assert tp == [1, 4]
    assert any(p.cell_id.endswith("/shards4")
               for p in by_target["train_payload"])
    assert not any("/shards" in p.cell_id and p.data_shards == 1
                   for p in plans)
    # single-device target: one cell, sweep logged
    assert [p.data_shards for p in by_target["gemm_packed"]] == [1]
    assert any("cannot shard its collective" in s["reason"]
               for s in skipped)


def test_mesh_values_validated():
    with pytest.raises(ValueError):
        CampaignSpec(name="t", targets=("train_payload",), mesh=(0,))


def test_multidevice_grid_expands_with_sharded_and_contrast_cells():
    all_plans = []
    for s in multidevice_specs(seed=0, quick=True):
        plans, _ = expand(s)
        all_plans += plans
    targets = {p.target for p in all_plans}
    assert {"train_payload_shard", "train_reduced",
            "train_payload"} <= targets
    shard_counts = {(p.target, p.data_shards) for p in all_plans}
    # the contrast pair: same seam with and without a real collective
    assert ("train_payload", 1) in shard_counts
    assert ("train_payload", 4) in shard_counts
    assert all(p.data_shards == 4 for p in all_plans
               if p.target in ("train_payload_shard", "train_reduced"))


def test_new_seam_targets_registered_with_bounds():
    ps = get_target("train_payload_shard")
    assert ps.shardable and ps.soak is not None
    assert ps.analytic_bound(_plan("train_payload_shard")) == 1.0
    rd = get_target("train_reduced")
    assert rd.shardable
    assert rd.analytic_bound(
        _plan("train_reduced", dtype="int32")) == 0.0


# ---------------------------------------------------------------------------
# executor: device-count validation + mesh-slice placement fallback
# ---------------------------------------------------------------------------

def test_resolve_device_count_falls_back_with_warning():
    import jax
    avail = jax.local_device_count()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert resolve_device_count(avail + 7) == avail
    assert any("falling" in str(x.message) for x in w)
    # in-range requests are trusted; None means "all"
    assert resolve_device_count(None) == avail
    assert resolve_device_count(1) == 1


def test_resolve_device_count_strict_raises():
    import jax
    avail = jax.local_device_count()
    with pytest.raises(RuntimeError, match="only"):
        resolve_device_count(avail + 1, strict=True)
    assert resolve_device_count(avail, strict=True) == avail


def test_cell_mesh_clamps_to_available_devices():
    import jax
    if jax.local_device_count() > 1:
        pytest.skip("needs a single-device host to exercise the clamp")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        mesh, shards = _cell_mesh(_plan(shards=4))
    assert mesh is None and shards == 1
    assert any("data_shards" in str(x.message) for x in w)
    # unsharded plans never warn
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert _cell_mesh(_plan(shards=1)) == (None, 1)


@pytest.mark.slow
def test_sharded_plan_degrades_to_single_device_cell():
    """data_shards=4 on a 1-device host must still produce a valid cell
    (the payload seam degenerates to the single-device verify) with the
    degradation recorded, not a Mesh/pmap shape error."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        r = run_cell(_plan(shards=4, samples=2), chunk=4)
    m = r.metrics
    assert m.shards == 1 and m.collective_verified is False
    assert m.raw_detection_rate == 1.0      # bound holds even degraded
    assert m.shard_detections is None


# ---------------------------------------------------------------------------
# end to end: a sharded soak cell on a real 4-device mesh (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_cell_end_to_end_four_device_subprocess():
    """The acceptance cell: a training-soak cell with data_shards=4 runs
    checked_psum through a REAL shard_map psum, detects a single-shard
    int8 payload flip after the collective with latency 0 recorded in
    the soak histogram, and attributes the corruption to shard 0."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        from repro.campaign import run_cell
        from repro.campaign.spec import CellPlan, cell_seed

        cid = "e2e/train_payload_shard"
        plan = CellPlan(
            cell_id=cid, target="train_payload_shard",
            fault_model="bitflip", bit_band="significant", shape=(2, 8),
            dtype="int8", samples=2, clean_samples=1, flips=1,
            seed=cell_seed(0, cid), measure_overhead=False, steps=2,
            data_shards=4)
        m = run_cell(plan, chunk=4).metrics
        print("METRICS=" + json.dumps(m.to_dict()))
    """)
    env = {**os.environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("METRICS=")]
    assert line, (r.stdout[-2000:], r.stderr[-2000:])
    m = json.loads(line[0][len("METRICS="):])
    assert m["shards"] == 4 and m["collective_verified"] is True
    assert m["raw_detection_rate"] == 1.0
    assert m["escapes"] == 0 and m["false_positives"] == 0
    assert m["detection_latency_hist"] == [2, 0]    # caught in-step
    assert m["mean_detection_latency"] == 0.0
    assert m["shard_detections"] == [2, 0, 0, 0]    # blames shard 0


# ---------------------------------------------------------------------------
# artifact rendering: the shards column
# ---------------------------------------------------------------------------

def test_latency_markdown_renders_shards_column():
    result = {
        "campaign": "t",
        "cells": [{
            "cell_id": "train_payload_shard/x/steps2/shards4",
            "plan": {},
            "metrics": {
                "steps": 2, "detection_latency_hist": [2, 0],
                "mean_detection_latency": 0.0, "divergence_mean": 1e-5,
                "divergence_max": 2e-5, "loss_divergence_mean": 1e-4,
                "shards": 4, "collective_verified": True,
                "shard_detections": [2, 0, 0, 0]},
        }, {
            "cell_id": "train_payload/x/steps2",
            "plan": {},
            "metrics": {
                "steps": 2, "detection_latency_hist": [2, 0],
                "mean_detection_latency": 0.0, "divergence_mean": 0.0,
                "divergence_max": 0.0, "loss_divergence_mean": 0.0,
                "shards": 1, "collective_verified": False,
                "shard_detections": None},
        }],
    }
    md = latency_markdown(result)
    assert "| shards |" in md.splitlines()[2]
    assert "4✓ [2 0 0 0]" in md
    assert "| 1 |" in md
