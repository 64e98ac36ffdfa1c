"""Training driver: ``python -m repro.launch.train --arch llama3.2-1b ...``

Composes the full stack: config -> model -> mesh/shardings -> jitted
train step -> seeded data pipeline -> fault-tolerant loop (ABFT metrics,
detect->recompute, checksummed async checkpoints, straggler telemetry).

Defaults are sized for the in-container CPU (1 device, reduced configs via
``--smoke``); on a real pod the same flags drive the production mesh.
"""
from __future__ import annotations

# ruff: noqa: E402
import argparse
import dataclasses
import logging
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--smoke", action="store_true",
                    help="reduce the arch to smoke size (CPU-runnable)")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--mesh-shape", default="1,1",
                    help="host mesh (data,model), e.g. 2,2")
    ap.add_argument("--float-abft", action="store_true",
                    help="float ABFT checks on training GEMMs")
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient compression with "
                         "mod-checksum verification (runtime.compression; "
                         "comm/errors feeds the loop's fault policy)")
    ap.add_argument("--fault-policy", default="recompute",
                    choices=["log", "recompute", "restore"])
    ap.add_argument("--device-count", type=int, default=0,
                    help="force N host devices (set before jax init)")
    args = ap.parse_args()

    if args.device_count:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.device_count}")

    import jax
    import jax.numpy as jnp

    from repro.compile_cache import enable_compile_cache
    from repro.configs import reduce_cfg
    from repro.configs.base import ShapeConfig
    from repro.configs.registry import get_arch
    from repro.data import make_dataset
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.launch.steps import (init_train_state, make_train_step,
                                    train_state_lp)
    from repro.layers.common import Ctx
    from repro.models.base import build_model
    from repro.runtime import LoopConfig, TrainLoop
    from repro.sharding import shardings_of
    from repro.sharding.rules import train_rules

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    log = logging.getLogger("repro.train")
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduce_cfg(cfg)
    if args.accum > 1:
        cfg = dataclasses.replace(cfg, train_accum=args.accum)

    shape = ShapeConfig("cli", "train", args.seq_len, args.batch)
    model = build_model(cfg, max_pos=args.seq_len + cfg.meta_tokens + 8)

    if args.mesh == "host":
        mshape = tuple(int(x) for x in args.mesh_shape.split(","))
        mesh = make_host_mesh(mshape)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    rules = train_rules(args.mesh == "multi")
    ctx = Ctx(rules=rules, quant=False, float_abft=args.float_abft,
              compute_dtype=jnp.bfloat16)

    step_fn = make_train_step(model, ctx, accum=cfg.train_accum,
                              peak_lr=args.lr, total_steps=args.steps,
                              compress=args.compress)
    state_lp = train_state_lp(model, compress=args.compress)
    state_sh = shardings_of(state_lp, rules, mesh)
    batch_sh = shardings_of(model.input_specs(shape), rules, mesh)

    with jax.set_mesh(mesh):
        state = init_train_state(model, jax.random.key(0),
                                 compress=args.compress)
        state = jax.device_put(state, state_sh)
        jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))

        dataset = make_dataset(cfg, shape)
        n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
        log.info("arch=%s params=%.1fM mesh=%s accum=%d",
                 cfg.name, n_params / 1e6, mesh.shape, cfg.train_accum)

        def hook(step, metrics):
            log.info("step %d loss=%.4f gnorm=%.3f gemm_err=%d eb_err=%d"
                     " comm_err=%d",
                     step, float(metrics.get("loss_final", float("nan"))),
                     float(metrics.get("grad_norm", float("nan"))),
                     int(metrics.get("abft/gemm_errors", 0)),
                     int(metrics.get("abft/eb_errors", 0)),
                     int(metrics.get("comm/errors", 0)))

        loop = TrainLoop(
            jitted, dataset,
            cfg=LoopConfig(ckpt_dir=args.ckpt_dir,
                           save_every=args.save_every,
                           fault_policy=args.fault_policy),
            shardings=batch_sh, metrics_hook=hook)
        state, metrics = loop.run(state, args.steps)
        log.info("done: %s | loop stats %s",
                 {k: float(v) for k, v in metrics.items()
                  if k in ("loss_final", "grad_norm")}, loop.stats)


if __name__ == "__main__":
    main()
