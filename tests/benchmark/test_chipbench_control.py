"""The comparison that decides ``correct`` fails what it must fail.

At a tiny size on the CPU: the int4 control in the program's place, and
the timed path broken underneath (``faults.FAULTS``: an answer altered
where it is produced, the checks silenced, a wrong row or a missing row
in every EmbeddingBag), each make ``correct`` false; the plain reference
draws the program's own weights from the seed."""
from __future__ import annotations

import numpy as np
import pytest

import chipbench_tiny as tiny
import faults


def test_int4_control_fails_the_logit_limit(tmp_path):
    res = tiny.run(tmp_path, seed=7, control_bits=4)
    limit = tiny.LIMITS["logit_gap"]["limit"]
    assert res["correct"] is True
    assert res["checks"]["logit_gap"]["value"] <= limit
    assert res["control"]["logit_gap"] > 3 * limit
    assert res["control"]["correct"] is False


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    with faults.plant(fault):
        res = tiny.run(tmp_path, seed=8)
    assert res["correct"] is False
    bad = {k for k, c in res["checks"].items()
           if not (c["value"] <= c["limit"] if c["pass_if"] == "<="
                   else c["value"] >= c["limit"])}
    assert bad == ({"injected_flip_flags"} if fault == "flags_silenced"
                   else {"logit_gap"})


def test_reference_draws_the_programs_weights():
    import jax

    from reference import dlrm as ref
    from runners.dlrm_serving import extras

    from repro.models.dlrm import init_dlrm
    from repro.sharding import values_of

    cfg, seed = tiny.CONFIG, 123
    p = values_of(init_dlrm(jax.random.key(seed), extras(cfg), quant=True))
    bottom, top = ref.mlp_weights(seed, cfg)
    for got, (w, a) in zip(p["bottom"] + p["top"], bottom + top):
        n = w.shape[1]
        np.testing.assert_array_equal(np.asarray(got["w_packed"])[:, :n],
                                      np.asarray(w))
        np.testing.assert_array_equal(np.asarray(got["alpha"]),
                                      np.asarray(a))
    idx = np.array([0, 5, 1023], np.int32)
    keys = jax.random.split(ref.keys(seed)[2], cfg["n_tables"])
    for t in range(cfg["n_tables"]):
        q, a, b = ref._table_rows(keys[t], idx, cfg["table_rows"],
                                  cfg["emb_dim"])
        tab = p["tables"]
        np.testing.assert_array_equal(np.asarray(tab["table"][t])[idx],
                                      np.asarray(q))
        np.testing.assert_array_equal(np.asarray(tab["alphas"][t])[idx],
                                      np.asarray(a))
        np.testing.assert_array_equal(np.asarray(tab["betas"][t])[idx],
                                      np.asarray(b))
