"""BENCHMARK.json keeps to its schema, and every name in it has its files."""
from __future__ import annotations

import json
import re

import pytest

import chipbench_tiny as tiny

SPEC = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: keys that are widths, which a cut may never change
WIDTHS = ("n_dense", "bottom_mlp", "emb_dim", "top_mlp")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3"
    assert all((tiny.REPO / p).is_dir() for p in SPEC["paths"])
    assert (tiny.REPO / SPEC["command"][1]).is_file()
    # a full check of 24 cells fits its time
    rs = SPEC["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lengths():
    for sect in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[sect]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_have_readers_and_move_a_metric():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert any("mfu" in n for names in layers.values() for n in names)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_files(cfg):
    data = json.loads((tiny.REPO / cfg["file"]).read_text())
    assert cfg["file"].startswith(SPEC["paths"][0] + "/")
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    assert not set(cfg["reduced"]) & set(WIDTHS)
    assert (tiny.BENCH / "runners" / f"{data['runner']}.py").is_file()
    for key in cfg["reduced"]:
        assert key in data["published"]


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cells(cell):
    assert cell["chips"] in (1, 4)
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    assert (tiny.BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    limits = json.loads((tiny.BENCH / "limits" / f"{cell['name']}.json")
                        .read_text())
    for name, lim in limits.items():
        assert lim["pass_if"] in ("<=", ">=")
        assert isinstance(lim["limit"], (int, float))
    assert {"logit_gap", "clean_flags", "injected_flip_flags"} <= set(limits)
