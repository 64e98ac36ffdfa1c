"""The chip benchmark's harness runs as data, and refuses to run off-chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny as tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return tiny.run(tmp_path_factory.mktemp("cell"), seed=3)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return tiny.run(tmp_path_factory.mktemp("cell"), seed=4, trace=True)


def test_untraced_run_reports_the_end_to_end_metrics(untraced):
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    assert list(untraced)[:5] == KEYS and list(untraced)[-1] == "checks"
    assert set(untraced) == set(KEYS) | {"checks"}
    assert set(untraced["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert untraced["correct"] is True
    assert untraced["attempted"] > 0 and untraced["failed"] == 0
    for m in spec["end_to_end"]:
        got = untraced["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0


def test_checks_carry_each_number_with_its_limit(untraced):
    checks = untraced["checks"]
    assert set(checks) == set(tiny.LIMITS)
    for name, c in checks.items():
        assert c["limit"] == tiny.LIMITS[name]["limit"]
        assert isinstance(c["value"], float)
    assert checks["injected_flip_flags"]["value"] >= 1
    assert checks["clean_flags"]["value"] == 0


def test_traced_run_reports_only_per_layer_metrics(traced):
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    names = {m["name"] for m in spec["per_layer"]}
    assert traced["correct"] is True
    assert set(traced["metrics"]) <= names
    # the CPU has no device trace: only the host- and counter-read ones
    assert {"host_ms_per_request", "abft_checks_per_request",
            "mfu"} <= set(traced["metrics"])
    dev = traced["device"]
    assert dev["window_s"] > 0 and "busy_s" in dev
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_new_cell_needs_only_new_files(tmp_path):
    """The tiny configuration, traffic and limits exist only as files in a
    fresh directory; nothing in the harness names them."""
    import harness
    for code in ("harness.py", "runners/dlrm_serving.py", "traffic.py"):
        src = (tiny.BENCH / code).read_text()
        for name in ("tiny", "dlrm-table1", "dlrm-synth64", "pool100",
                     "onehot20"):
            assert name not in src, (code, name)
    tiny.write_cell(tmp_path)
    assert harness.find(tmp_path, "traffic", "mix", ".json").parent.parent \
        == tmp_path
    assert harness.find(tmp_path, "runners", "dlrm_serving", ".py").parent \
        == tiny.BENCH / "runners"
    with pytest.raises(FileNotFoundError):
        harness.find(tmp_path, "traffic", "no-such-mix", ".json")


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "dlrm-table1.pool100", "--seed", "5000000000", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_cli_without_a_tpu_exits_nonzero_and_prints_no_result():
    out = _cli(tiny.REPO)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "metrics" not in out.stdout and "{" not in out.stdout


def test_cli_alone_with_its_files_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    (no system under test) gives no result."""
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(tiny.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert "metrics" not in out.stdout
