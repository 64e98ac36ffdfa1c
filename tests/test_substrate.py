"""Tests: data pipeline, checkpoints (incl. corruption), compression,
straggler monitor, elastic planning, and the fault-tolerant loop."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import given, settings, st

from repro.checkpoint import (CheckpointCorruption, CheckpointManager,
                              latest_step, load_checkpoint, save_checkpoint)
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_arch
from repro.data import DataConfig, make_dataset
from repro.runtime import (LoopConfig, StragglerMonitor, TrainLoop,
                           init_compression, plan_remesh)
from repro.runtime.compression import (MOD, _mod_checksum, compress_grads,
                                       decompress_grads, verify_payload)


# ------------------------------- data ---------------------------------------

def test_lm_dataset_deterministic_and_shifted():
    ds = make_dataset(get_arch("llama3.2-1b"), ShapeConfig("t", "train", 64, 4))
    b0a, b0b, b1 = ds.batch_at(0), ds.batch_at(0), ds.batch_at(1)
    np.testing.assert_array_equal(b0a["tokens"], b0b["tokens"])
    assert not np.array_equal(b0a["tokens"], b1["tokens"])
    # labels are tokens shifted by one (same underlying stream)
    assert b0a["tokens"].shape == b0a["labels"].shape == (4, 64)
    np.testing.assert_array_equal(b0a["tokens"][:, 1:], b0a["labels"][:, :-1])


def test_vlm_encdec_dataset_shapes():
    vlm = make_dataset(get_arch("llava-next-mistral-7b"),
                       ShapeConfig("t", "train", 4096, 2))
    b = vlm.batch_at(3)
    cfg = get_arch("llava-next-mistral-7b")
    assert b["patches"].shape == (2, cfg.n_patches, cfg.patch_dim)
    assert b["tokens"].shape == (2, 4096 - cfg.n_patches)

    wh = make_dataset(get_arch("whisper-large-v3"),
                      ShapeConfig("t", "train", 64, 2))
    bw = wh.batch_at(0)
    assert bw["frames"].shape[1] == get_arch("whisper-large-v3").enc_seq


def test_dlrm_dataset_padding():
    ds = make_dataset(get_arch("dlrm"), ShapeConfig("t", "train", 1, 8))
    b = ds.batch_at(0, table_rows=500)
    assert b["bags"].shape == (26, 8, 128)
    assert (b["bags"] >= -1).all() and (b["bags"] < 500).all()
    # every bag has >= 1 valid index
    assert ((b["bags"] >= 0).sum(axis=-1) >= 1).all()


# ----------------------------- checkpoint ------------------------------------

def _state():
    return {"params": {"w": jnp.arange(24.0).reshape(4, 6),
                       "b": jnp.ones((6,), jnp.bfloat16)},
            "step": jnp.zeros((), jnp.int32)}


def test_ckpt_roundtrip_and_resume(tmp_path):
    base = str(tmp_path / "ck")
    st_ = _state()
    save_checkpoint(base, 5, st_)
    assert latest_step(base) == 5
    back = load_checkpoint(base, 5, jax.device_get(st_))
    np.testing.assert_allclose(np.asarray(back["params"]["w"]),
                               np.asarray(st_["params"]["w"]))
    assert back["params"]["b"].dtype == np.asarray(st_["params"]["b"]).dtype


def test_ckpt_detects_corruption_and_falls_back(tmp_path):
    base = str(tmp_path / "ck")
    st_ = _state()
    save_checkpoint(base, 1, st_)
    save_checkpoint(base, 2, st_)
    # flip a byte in the newest shard (silent data corruption in storage)
    shard = os.path.join(base, "step_000000002", "shard_00000.npz")
    data = bytearray(open(shard, "rb").read())
    data[len(data) // 2] ^= 0x40
    open(shard, "wb").write(bytes(data))
    with pytest.raises((CheckpointCorruption, Exception)):
        load_checkpoint(base, 2, jax.device_get(st_))
    mgr = CheckpointManager(base)
    restored, step = mgr.restore_latest(jax.device_get(st_))
    assert step == 1  # fell back past the corrupt step


def test_ckpt_torn_write_ignored(tmp_path):
    base = str(tmp_path / "ck")
    st_ = _state()
    save_checkpoint(base, 1, st_)
    # simulate a crash mid-save: step dir without COMMIT
    os.makedirs(os.path.join(base, "step_000000009"))
    assert latest_step(base) == 1


def test_ckpt_manager_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2, save_every=1)
    st_ = _state()
    for s in range(1, 6):
        mgr.maybe_save(s, st_)
    mgr.wait()
    mgr._gc()
    steps = sorted(n for n in os.listdir(str(tmp_path))
                   if n.startswith("step_") and not n.endswith(".tmp"))
    assert steps == ["step_000000004", "step_000000005"]


# ----------------------------- compression -----------------------------------

def test_mod_checksum_additivity():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-127, 128, (1000,), dtype=np.int32))
    b = jnp.asarray(rng.integers(-127, 128, (1000,), dtype=np.int32))
    lhs = int(_mod_checksum(a + b))
    rhs = (int(_mod_checksum(a)) + int(_mod_checksum(b))) % MOD
    assert lhs == rhs


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 2), st.integers(1, 4096))
def test_mod_checksum_matches_bigint(seed, n):
    rng = np.random.default_rng(seed)
    x = rng.integers(-(2 ** 20), 2 ** 20, (n,), dtype=np.int32)
    expect = int(sum(int(v) % MOD for v in x) % MOD)
    assert int(_mod_checksum(jnp.asarray(x))) == expect


def test_compress_error_feedback_converges():
    """With error feedback the quantization error does not accumulate:
    averaging compressed grads over steps approaches the true mean."""
    g = {"w": jnp.asarray(np.random.default_rng(1).standard_normal((64,)),
                          jnp.float32)}
    state = init_compression(g)
    acc = np.zeros((64,))
    steps = 50
    for _ in range(steps):
        payload, state = compress_grads(g, state)
        deq = np.asarray(payload["q"]["w"], np.float32) \
            * float(payload["scale"]["w"])
        acc += deq
    mean = acc / steps
    np.testing.assert_allclose(mean, np.asarray(g["w"]), atol=2e-2)


def test_verify_payload_detects_flip():
    g = {"w": jnp.ones((32,), jnp.float32)}
    payload, _ = compress_grads(g, init_compression(g))
    assert int(verify_payload(payload)) == 0
    bad = dict(payload)
    q = np.asarray(payload["q"]["w"]).copy()
    q[3] ^= 0x10   # bit flip in transported payload
    bad["q"] = {"w": jnp.asarray(q)}
    assert int(verify_payload(bad)) == 1


def test_checked_psum_multidevice_subprocess():
    """checked_psum under shard_map on 4 host devices (subprocess sets
    XLA_FLAGS before jax init)."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.sharding import shard_map
        from repro.runtime.compression import (compress_grads,
            init_compression, checked_psum, decompress_grads)
        mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("data",))
        gs = jnp.stack([jnp.full((8,), float(i + 1)) for i in range(4)])

        @partial(shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=(P(), P()))
        def reduce(g_shard):
            g = {"w": g_shard[0]}
            payload, _ = compress_grads(g, init_compression(g))
            summed, ssum, errs = checked_psum(payload, "data")
            mean = decompress_grads(summed, ssum, 4)
            return mean["w"], errs
        mean, errs = reduce(gs)
        np.testing.assert_allclose(np.asarray(mean), 2.5, atol=0.05)
        assert int(errs) == 0
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "OK" in r.stdout, r.stderr[-2000:]


# ----------------------------- straggler -------------------------------------

def test_straggler_monitor_flags_slow_steps():
    mon = StragglerMonitor(window=50, threshold=2.0, patience=2)
    for i in range(20):
        assert mon.observe(i, 1.0) is None
    ev = mon.observe(20, 3.0)
    assert ev is not None and ev["ratio"] == pytest.approx(3.0)
    fired = []
    mon.on_straggler = fired.append
    mon.observe(21, 3.0)
    assert fired and fired[0]["consecutive"] == 2


def test_straggler_host_attribution():
    mon = StragglerMonitor(window=50, threshold=2.0)
    for i in range(20):
        mon.observe(i, 1.0)
    ev = mon.observe(20, 5.0,
                     host_times={0: 1.0, 1: 1.1, 2: 5.0, 3: 0.9})
    assert ev["slow_hosts"] == [2]


# ---------------------------- compile cache ----------------------------------

@pytest.fixture
def cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_defaults_to_checkout(cache_config, monkeypatch):
    from repro.compile_cache import CHECKOUT, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert (CHECKOUT / "src" / "repro" / "compile_cache.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path      # fixed: same on every call


def test_compile_cache_env_dir_wins_untouched(cache_config, monkeypatch,
                                              tmp_path):
    from repro.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


# ------------------------------ elastic --------------------------------------

def test_plan_remesh_shrinks_data_axis():
    plan = plan_remesh(512, model_parallel=16)
    assert plan.new_shape == (32, 16)
    plan2 = plan_remesh(500, model_parallel=16)   # 12 hosts died
    assert plan2.new_shape == (31, 16) and plan2.dropped_devices == 4
    with pytest.raises(ValueError):
        plan_remesh(8, model_parallel=16)


# ------------------------------- loop ----------------------------------------

def test_train_loop_runs_resumes_and_recomputes(tmp_path):
    """Tiny quadratic 'model'; a fault injected via metrics at one step
    triggers recompute; crash-restart resumes from checkpoint."""
    calls = {"n": 0}

    def step_fn(state, batch):
        calls["n"] += 1
        w = state["w"] - 0.1 * (state["w"] - batch["x"].mean())
        # simulated detected soft error at exactly one (step, first try)
        faulty = (int(state["step"]) == 3 and calls.setdefault("f", 0) == 0)
        if faulty:
            calls["f"] = 1
        m = {"abft/gemm_errors": jnp.asarray(1 if faulty else 0, jnp.int32),
             "loss": jnp.mean((w - batch["x"].mean()) ** 2)}
        return {"w": w, "step": state["step"] + 1}, m

    class DS:
        def batch_at(self, step):
            rng = np.random.default_rng(step)
            return {"x": jnp.asarray(rng.standard_normal(8), jnp.float32)}

    cfg = LoopConfig(ckpt_dir=str(tmp_path / "ck"), save_every=2,
                     fault_policy="recompute", log_every=100)
    loop = TrainLoop(step_fn, DS(), cfg=cfg)
    state0 = {"w": jnp.zeros(()), "step": jnp.zeros((), jnp.int32)}
    state, _ = loop.run(state0, 6)
    assert int(state["step"]) == 6
    assert loop.stats["recomputes"] == 1 and loop.stats["faulty_steps"] == 1

    # "crash": new loop resumes from committed step 6, runs to 8
    loop2 = TrainLoop(step_fn, DS(), cfg=cfg)
    state2, _ = loop2.run(state0, 8)
    assert int(state2["step"]) == 8
