"""A tiny benchmark cell, written as files, for CPU tests of the harness.

The cell's configuration, traffic, limits, peaks and ``BENCHMARK.json``
are new files in a directory of their own; the harness finds them by
name, as it finds the real cells'."""
from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
for p in (BENCH, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

CONFIG = {"name": "tiny", "source": "test", "runner": "dlrm_serving",
          "n_dense": 13, "bottom_mlp": [32, 16], "n_tables": 3,
          "table_rows": 1024, "emb_dim": 16, "pooling": 8,
          "top_mlp": [32, 1], "batch": 4, "reduced": []}
TRAFFIC = {"kind": "dlrm_lookup", "batch": "config",
           "pooling": {"law": "fixed", "value": 8}, "ids": {"law": "uniform"},
           "dense": {"law": "normal"}, "arrival": {"law": "closed"}}
WORKLOAD = "tiny.mix"
#: the real cells' limits, with a tiny cell's logit gap (CPU, XLA paths:
#: 0.018-0.042 over seeds 1-6; the int4 control 0.49-0.65)
LIMITS = {"logit_gap": {"limit": 0.1, "pass_if": "<="},
          "clean_flags": {"limit": 0, "pass_if": "<="},
          "aborted": {"limit": 0, "pass_if": "<="},
          "injected_flip_flags": {"limit": 1, "pass_if": ">="}}


def write_cell(root: Path) -> Path:
    """Write the tiny cell's files under ``root``; -> its BENCHMARK.json."""
    for d in ("configs", "traffic", "limits"):
        (root / d).mkdir(parents=True, exist_ok=True)
    (root / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (root / "traffic" / "mix.json").write_text(json.dumps(TRAFFIC))
    (root / "limits" / f"{WORKLOAD}.json").write_text(json.dumps(LIMITS))
    (root / "peaks.json").write_text(json.dumps({"cpu": {
        "bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
        "hbm_bytes_per_s": 1e11, "source": "test"}}))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": WORKLOAD, "config": "tiny",
                          "traffic": "mix", "chips": 1, "why": "test"}]
    for m in spec["per_layer"] + spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [WORKLOAD]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


def run(root: Path, seed: int = 3, seconds: float = 0.3,
        trace: bool = False, **kw) -> dict:
    import harness
    spec = write_cell(root)
    return harness.run(WORKLOAD, seed, seconds, trace, spec_path=spec,
                       root=root, peaks_path=root / "peaks.json",
                       require_tpu=False, log=lambda msg: None, **kw)
