"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be run as a module entry point (``python -m repro.launch.dryrun``) —
the first two lines below force 512 host platform devices BEFORE any jax
initialization so ``make_production_mesh`` can build the production meshes:
(16,16)=("data","model") single-pod and (2,16,16)=("pod","data","model")
multi-pod.

Per cell this produces ``artifacts/dryrun/<arch>__<shape>__<mesh>.json``:
  * compile success (sharding coherence proof) + compile wall time,
  * ``memory_analysis()``  — per-device bytes (fits-in-HBM proof),
  * trip-aware cost analysis (launch.costs) — flops / HBM bytes /
    collective bytes per device,
  * analytic MODEL_FLOPS and params (launch.roofline),
  * the collective schedule breakdown.

Skips (recorded, per DESIGN.md §Arch-applicability):
  * ``long_500k`` for pure full-attention archs (O(S²)/O(S·cache) decode at
    524k is out of scope by assignment; sub-quadratic archs run it).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# ruff: noqa: E402
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES
from repro.configs.registry import get_arch, list_archs
from repro.launch.costs import analyze_hlo_text
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (count_params, model_flops,
                                   roofline_terms)
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step, make_train_step_deferred)
from repro.layers.common import Ctx
from repro.models.base import Model, build_model
from repro.sharding import shardings_of, values_of
from repro.sharding.rules import serve_rules, train_rules

LM_ARCHS = [a for a in list_archs() if a != "dlrm"]


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


def cell_skip_reason(cfg, shape) -> str | None:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("full-attention: 524k dense-KV decode excluded by "
                "assignment; sub-quadratic archs (rwkv6, hymba) run it")
    return None


def _tree_shardings(lp_tree, rules, mesh):
    return shardings_of(lp_tree, rules, mesh)


#: Ctx overrides for A/B perf runs (set by --set k=v; EXPERIMENTS §Perf).
CTX_OVERRIDES: dict = {}


def _ctx(**kw) -> Ctx:
    import dataclasses as _dc
    fields = {f.name for f in _dc.fields(Ctx)}
    ov = {k: v for k, v in CTX_OVERRIDES.items() if k in fields}
    return Ctx(**kw).replace(**ov)


def build_train(model: Model, shape, rules, mesh):
    cfg = model.cfg
    ctx = _ctx(rules=rules, quant=False, abft=False, float_abft=False,
               compute_dtype=jnp.bfloat16,
               wkv_chunk=cfg.wkv_chunk, ssm_chunk=cfg.ssm_chunk)
    # microbatches must stay shardable over the batch axes: clamp accum so
    # global_batch/accum is a multiple of the data(+pod) extent
    msz = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_batch_shards = msz.get("data", 1) * msz.get("pod", 1)
    accum = max(1, min(cfg.train_accum, shape.global_batch // n_batch_shards))
    while shape.global_batch // accum % n_batch_shards:
        accum -= 1

    from repro.launch.steps import train_state_lp
    state_lp = train_state_lp(model)
    params_lp = state_lp["params"]
    batch_lp = model.input_specs(shape)

    if CTX_OVERRIDES.get("zero1") or cfg.zero1:
        # hillclimb 2, iteration 4: pure DP over every mesh axis + ZeRO-1
        # flat-sharded optimizer — zero per-microbatch collectives
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.steps import (make_train_step_zero1,
                                        zero1_state_sds)
        axes = (("pod", "data", "model") if "pod" in mesh.axis_names
                else ("data", "model"))
        ctx = ctx.replace(rules=None)
        step_fn = make_train_step_zero1(model, ctx, mesh, accum=1,
                                        axes=axes)
        state_sds, state_sh, params_lp = zero1_state_sds(model, mesh,
                                                         axes=axes)
        axis = tuple(axes)
        batch_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, P(axis)), values_of(batch_lp))
        jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        lowered = jitted.lower(state_sds, values_of(batch_lp))
        return lowered, params_lp

    if CTX_OVERRIDES.get("deferred_sync") or cfg.deferred_grad_sync:
        # hillclimb 2: manual data axis, one int8+checksum grad collective
        # per step, params replicated over data (no ZeRO) — see
        # steps.make_train_step_deferred
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.steps import init_comm_state
        ctx = ctx.replace(rules={**rules, "embed": None})
        repl_rules = {**rules, "embed": None}
        data_axes = (("pod", "data") if "pod" in mesh.axis_names
                     else ("data",))
        step_fn = make_train_step_deferred(
            model, ctx, mesh, accum=accum, data_axes=data_axes)
        state_sh = _tree_shardings(state_lp, repl_rules, mesh)
        state_sds = values_of(state_lp)
        n_data = 1
        for a in data_axes:
            n_data *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
        comm_sds = init_comm_state(state_sds["params"], n_data)
        # residuals shard over data on the stack dim AND over model via the
        # parameter's own logical axes (a full-f32 per-device residual set
        # would blow HBM on its own)
        from repro.runtime.compression import CompressionState
        from repro.sharding import LogicalParam, is_lp
        comm_lp = CompressionState(error=jax.tree.map(
            lambda p: LogicalParam(
                jax.ShapeDtypeStruct((n_data,) + p.value.shape, jnp.float32),
                ("comm_stack",) + p.axes),
            params_lp, is_leaf=is_lp))
        comm_rules = {**repl_rules, "comm_stack": data_axes}
        comm_sh = _tree_shardings(comm_lp, comm_rules, mesh)
        batch_sh = _tree_shardings(batch_lp, repl_rules, mesh)
        jitted = jax.jit(step_fn,
                         in_shardings=(state_sh, comm_sh, batch_sh),
                         out_shardings=(state_sh, comm_sh, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(state_sds, comm_sds, values_of(batch_lp))
        return lowered, params_lp

    step_fn = make_train_step(model, ctx, accum=accum)
    state_sh = _tree_shardings(state_lp, rules, mesh)
    state_sds = values_of(state_lp)
    batch_sh = _tree_shardings(batch_lp, rules, mesh)
    batch_sds = values_of(batch_lp)

    jitted = jax.jit(step_fn,
                     in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,))
    lowered = jitted.lower(state_sds, batch_sds)
    return lowered, params_lp


def build_prefill(model: Model, shape, rules, mesh):
    cfg = model.cfg
    ctx = _ctx(rules=rules, quant=True, abft=True,
               compute_dtype=jnp.bfloat16,
               wkv_chunk=cfg.wkv_chunk, ssm_chunk=cfg.ssm_chunk)
    step_fn = make_prefill_step(model, ctx, cache_len=shape.seq_len)
    params_lp = jax.eval_shape(
        lambda: model.init(jax.random.key(0), quant=True))
    params_sh = _tree_shardings(params_lp, rules, mesh)
    batch_lp = model.input_specs(shape)
    batch_sh = _tree_shardings(batch_lp, rules, mesh)

    jitted = jax.jit(step_fn, in_shardings=(params_sh, batch_sh),
                     out_shardings=None)
    lowered = jitted.lower(values_of(params_lp), values_of(batch_lp))
    return lowered, params_lp


def build_decode(model: Model, shape, rules, mesh):
    ctx = _ctx(rules=rules, quant=True, abft=True,
               compute_dtype=jnp.bfloat16)
    step_fn = make_decode_step(model, ctx)
    params_lp = jax.eval_shape(
        lambda: model.init(jax.random.key(0), quant=True))
    params_sh = _tree_shardings(params_lp, rules, mesh)
    B = shape.global_batch
    cache_lp = jax.eval_shape(
        lambda: model.init_cache(B, shape.seq_len))
    cache_sh = _tree_shardings(cache_lp, rules, mesh)
    batch_lp = model.input_specs(shape)
    batch_sh = _tree_shardings(batch_lp, rules, mesh)

    jitted = jax.jit(
        step_fn,
        in_shardings=(params_sh, cache_sh, batch_sh["tokens"],
                      batch_sh["pos"]),
        out_shardings=(None, cache_sh, None),
        donate_argnums=(1,))
    lowered = jitted.lower(values_of(params_lp), values_of(cache_lp),
                           values_of(batch_lp)["tokens"],
                           values_of(batch_lp)["pos"])
    return lowered, params_lp


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str, *, skip_existing: bool = False) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out_path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    if skip_existing and os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)

    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
    }
    reason = cell_skip_reason(cfg, shape)
    if reason:
        rec.update(status="skipped", skip_reason=reason)
        _write(out_path, rec)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rec["n_devices"] = int(n_dev)
    rules = (train_rules(multi_pod) if shape.kind == "train"
             else serve_rules(multi_pod))
    if (CTX_OVERRIDES.get("seq_parallel", cfg.seq_parallel)
            and shape.kind == "train"):
        rules = {**rules, "seq": "model"}
    if CTX_OVERRIDES.get("moe_token_parallel",
                         cfg.moe_token_parallel) and shape.kind == "train":
        rules = {**rules, "expert": None, "expert_mlp": None,
                 "moe_tokens": "model"}
    max_pos = max(shape.seq_len, 4096) + cfg.meta_tokens + 1
    model = build_model(cfg, max_pos=max_pos)

    deferred = bool(CTX_OVERRIDES.get("deferred_sync")
                    or cfg.deferred_grad_sync)
    t0 = time.time()
    try:
        import contextlib
        # the deferred (shard_map) path lowers without the ambient concrete
        # mesh: its shardings carry the mesh, and an ambient (Auto,Auto)
        # mesh conflicts with the (Manual,Auto) abstract mesh inside
        cm = contextlib.nullcontext() if (deferred and shape.kind ==
                                          "train") else jax.set_mesh(mesh)
        with cm:
            if shape.kind == "train":
                lowered, params_lp = build_train(model, shape, rules, mesh)
            elif shape.kind == "prefill":
                lowered, params_lp = build_prefill(model, shape, rules, mesh)
            else:
                lowered, params_lp = build_decode(model, shape, rules, mesh)
            compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 — recorded as cell failure
        rec.update(status="failed", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-3000:])
        _write(out_path, rec)
        return rec
    rec["compile_seconds"] = round(time.time() - t0, 1)

    ma = compiled.memory_analysis()
    rec["memory_per_device"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_estimate_bytes": int(ma.argument_size_in_bytes
                                   + ma.output_size_in_bytes
                                   + ma.temp_size_in_bytes
                                   - ma.alias_size_in_bytes),
    }
    xla_cost = compiled.cost_analysis()
    rec["xla_cost_once"] = {
        "flops": float(xla_cost.get("flops", -1)),
        "bytes_accessed": float(xla_cost.get("bytes accessed", -1)),
    }

    t1 = time.time()
    cost = analyze_hlo_text(compiled.as_text(), n_partitions=n_dev)
    rec["analyze_seconds"] = round(time.time() - t1, 1)
    rec["cost_per_device"] = cost
    rec["roofline"] = roofline_terms(cost, n_devices=n_dev)

    # analytic useful-work floor
    active_frac = (cfg.top_k / cfg.n_experts) if cfg.n_experts else None
    params = count_params(params_lp, active_moe=active_frac)
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind != "decode" else shape.global_batch)
    mf = model_flops(shape.kind, params["active"], tokens=tokens)
    rec["params"] = params
    rec["model_flops_global"] = mf
    hlo_global = cost["flops"] * n_dev
    rec["model_vs_hlo"] = mf / hlo_global if hlo_global else None
    rec["status"] = "ok"
    _write(out_path, rec)
    return rec


def _write(path: str, rec: dict):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=float)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None,
                    help="one arch id (default: all LM archs)")
    ap.add_argument("--shape", default=None,
                    help="one shape name (default: all four)")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    metavar="K=V", help="Ctx override, e.g. wkv_chunk=16")
    args = ap.parse_args()

    for kv in args.set:
        k, v = kv.split("=", 1)
        CTX_OVERRIDES[k] = (int(v) if v.lstrip("-").isdigit()
                            else v == "True" if v in ("True", "False")
                            else float(v) if "." in v else v)

    archs = [args.arch] if args.arch else LM_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.out,
                               skip_existing=args.skip_existing)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" compute={r['compute_s']:.3e}s "
                             f"mem={r['memory_s']:.3e}s "
                             f"coll={r['collective_s']:.3e}s "
                             f"dom={r['dominant']}"
                             f" compile={rec['compile_seconds']}s")
                elif status == "failed":
                    failures += 1
                    extra = " " + rec["error"][:160]
                print(f"[{status:7s}] {arch} × {shape} × "
                      f"{'multi' if mp else 'single'}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
