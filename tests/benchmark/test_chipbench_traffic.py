"""The benchmark's traffic generator: seeded, and as its files say."""
from __future__ import annotations

import json

import numpy as np
import pytest

import chipbench_tiny as tiny
import traffic

SPEC = json.loads((tiny.REPO / "BENCHMARK.json").read_text())


def _cell(name):
    cell = next(w for w in SPEC["workloads"] if w["name"] == name)
    cfg = json.loads((tiny.BENCH / "configs" / f"{cell['config']}.json")
                     .read_text())
    mix = json.loads((tiny.BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return cfg, mix


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_streams_match_their_traffic_files(name):
    cfg, mix = _cell(name)
    s = traffic.make_stream(mix, cfg, 4_100_000_000)
    b = traffic.batch_of(mix, cfg)
    pool = traffic.pool_of(mix)
    assert s.batch == b and s.pool == pool
    assert mix["pooling"]["law"] == "fixed"
    ps = [s.payload(i) for i in (0, 1, 10_000)]
    for p in ps:
        assert p["dense"].shape == (b, cfg["n_dense"])
        assert p["dense"].dtype == np.float32
        assert p["bags"].shape == (cfg["n_tables"], b, pool)
        assert p["bags"].dtype == np.int32
        assert p["bags"].min() >= 0 and p["bags"].max() < cfg["table_rows"]
    dense = np.concatenate([p["dense"].reshape(-1) for p in ps])
    assert abs(float(dense.mean())) < 0.1
    assert abs(float(dense.std()) - 1) < 0.1
    assert s.due_s(5) is None                 # closed loop


def test_a_seed_reproduces_its_requests_and_another_does_not():
    cfg, mix = tiny.CONFIG, tiny.TRAFFIC
    a = traffic.make_stream(mix, cfg, 2**31 + 17)
    b = traffic.make_stream(mix, cfg, 2**31 + 17)
    c = traffic.make_stream(mix, cfg, 2**31 + 18)
    for i in (0, 5, 40):
        np.testing.assert_array_equal(a.payload(i)["bags"],
                                      b.payload(i)["bags"])
        np.testing.assert_array_equal(a.payload(i)["dense"],
                                      b.payload(i)["dense"])
        assert not np.array_equal(a.payload(i)["bags"], c.payload(i)["bags"])


def test_no_request_of_a_window_repeats():
    s = traffic.make_stream(tiny.TRAFFIC, tiny.CONFIG, 5)
    seen = {s.payload(i)["bags"].tobytes() for i in range(2000)}
    assert len(seen) == 2000


@pytest.mark.parametrize("law", ["poisson", "bursty"])
def test_open_loop_arrivals_keep_their_rate(law):
    mix = dict(tiny.TRAFFIC, arrival={"law": law, "rate_rps": 200.0,
                                      "burst": 8, "spread_s": 1e-3})
    s = traffic.make_stream(mix, tiny.CONFIG, 11)
    due = s.due_s(40000)
    assert (np.diff(due) >= 0).all()
    assert 40000 / due[-1] == pytest.approx(200.0, rel=0.05)
    np.testing.assert_array_equal(due, s.due_s(40000))


def test_unknown_laws_are_refused():
    for key, bad in (("arrival", {"law": "trace"}), ("ids", {"law": "zipf"}),
                     ("kind", "chat")):
        with pytest.raises(ValueError):
            traffic.make_stream(dict(tiny.TRAFFIC, **{key: bad}),
                                tiny.CONFIG, 1)
