#!/usr/bin/env python3
"""On-chip smoke test of the protected main paths.

    python chip_smoke.py                # one TPU chip: protected DLRM serving
    python chip_smoke.py --four-chips   # four chips: checked gradient psum

Run from the repository root.  Without options it serves seeded DLRM
lookups through :class:`repro.serving.ServingEngine` at the paper's table
geometry (26 tables x 4,000,000 rows x 128, int8; Table I pooling 100 and
batch 10) on two plan lanes: the default plan (fused Pallas kernels) and
``qgemm:scheme=xla,embedding_bag:scheme=xla`` (the XLA reference of
the same integer arithmetic, on the same tables).  It checks that their
pooled EmbeddingBag outputs and their logits agree, that the clean pass
raises no ABFT flag, and that an injected weight flip is detected.

``--four-chips`` runs only the cross-chip path users depend on, the int8
gradient all-reduce ``runtime.compression.checked_psum``:

- the collective alone: the same per-replica gradients compressed and
  summed over a 4-chip ``("data",)`` mesh and on one chip must give the
  same int32 payload sum (a control with one shard's gradient wrong must
  not), and one flipped shard payload must be caught after the collective;
- training: a few steps of ``launch.steps.make_train_step_deferred`` (the
  repo's shard_map train step) on the mesh against the same steps of
  ``make_train_step(compress=True)`` on one chip, with clean
  ``comm/errors == 0`` and parameters checked against a control in which
  one replica trains on another's batch.

The last line of stdout is a JSON verdict.  The script exits non-zero,
without a verdict, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GiB = 1 << 30
SEED = 0
#: memory left free on the chip when sizing the tables
SPARE_BYTES = GiB
#: DLRM lookups served per lane
N_REQUESTS = 24
#: the lanes' pooled EmbeddingBag outputs, compared in f32 before the cast
#: to the compute dtype.  Both sum the same 100 f32 terms per element, in
#: another order: ~1e-7 of the table's largest output.  A wrong row moves
#: an element by a whole term, ~1e-1 of it.
EB_RTOL = 1e-5
#: the XLA lane is the reference.  Both lanes share the int8 activation
#: quantizer and run exact int32 GEMMs, so only the f32 EmbeddingBag sum
#: order differs (bounded by EB_RTOL above).  But the pooled outputs are
#: cast to the serving compute dtype (bf16) before the interaction, and the
#: top-MLP input is cast and requantized again, so that difference can
#: move an element to the adjacent bf16 value and int8 step; each such
#: step shifts the logit by ~1e-3 of its scale, so 1e-2 of the batch's
#: logit scale bounds a few of them and still fails a wrong lookup.  (At
#: seed 0 a TPU v5e shows 5.7e-3.)
LOGIT_RTOL = 1e-2
#: ``--four-chips``: replicas, one per chip
N_CHIPS = 4
#: ``--four-chips``: layers of granite-moe-3b-a800m.  The one-chip
#: reference holds four replicas' f32 error-feedback residuals beside the
#: AdamW state; at two layers that needs 16.4 GB of the v5e's 15.75 GiB.
LAYERS = 1
#: ``--four-chips``: compared AdamW steps, their learning rate, and the
#: global batch (sequences x tokens)
STEPS = 3
LR = 3e-4
BATCH, SEQ = 8, 256
#: ``--four-chips``: the collective on identical inputs.  Each replica's
#: int8 payload is elementwise arithmetic and a max, the same in both
#: programs, so the int32 sums must match exactly; the four f32 scales may
#: be added in another order (a few ulp).
SCALE_RTOL = 1e-6
#: ``--four-chips``: the one-chip reference runs the same per-replica
#: steps as a vmap over the replica axis.  Its programs fuse differently,
#: and the model's attention scores and MoE dispatch run in bf16, so some
#: activations round to the adjacent bf16 value.  That can move a gradient
#: element across an int8 payload step, which AdamW turns into a step of
#: up to 2·LR: no absolute bound on |p4 - p1| holds a priori (8.2e-2 of
#: |p1 - p0| after 3 steps on a TPU v5e).  So the check is relative to a
#: control that differs by one shard: the same 1-chip steps with replica 0
#: fed replica 1's batch.  The 4-chip parameters must be this many times
#: closer to the reference than the control is.  (The transport itself is
#: held to the exact comparison above.)
PARAM_MARGIN = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    log(f"check {'PASS' if ok else 'FAIL'}: {what}")
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def tree_bytes(tree) -> int:
    import jax
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


# ------------------------------ one chip: DLRM -------------------------------

def dlrm_param_bytes(ex, rows: int) -> int:
    import jax

    from repro.models.dlrm import init_dlrm
    from repro.sharding import values_of
    return tree_bytes(jax.eval_shape(lambda: values_of(init_dlrm(
        jax.random.key(0), ex, quant=True, table_rows=rows))))


def fit_table_rows(ex, bytes_limit: int) -> tuple[int, str]:
    """Largest row count <= the paper's whose init fits with SPARE_BYTES
    free.  Init holds the stacked tables plus one table's temporaries
    (tables are generated one at a time), so the need is the parameters
    plus one table's share of them."""
    from repro.kernels.abft_embeddingbag import ROW_GROUP

    def need(rows):
        p = dlrm_param_bytes(ex, rows)
        return p + p // ex.n_tables

    rows = ex.table_rows
    budget = bytes_limit - SPARE_BYTES
    if need(rows) <= budget:
        return rows, (f"fits: init needs {need(rows) / 1e9:.2f} GB of "
                      f"{budget / 1e9:.2f} GB ({bytes_limit / 1e9:.2f} GB "
                      f"less 1 GiB spare)")
    full = need(rows)
    while rows > ROW_GROUP and need(rows) > budget:
        rows = int(rows * budget / need(rows)) // ROW_GROUP * ROW_GROUP
    check(rows >= ROW_GROUP and need(rows) <= budget,
          "the MLPs and some table rows fit the device")
    return rows, (f"CUT from {ex.table_rows} rows: init needs "
                  f"{full / 1e9:.2f} GB at full size, over "
                  f"{budget / 1e9:.2f} GB ({bytes_limit / 1e9:.2f} GB less "
                  f"1 GiB spare); widths unchanged")


def pooled_tables(params, bags, ctx):
    """Every table's pooled EmbeddingBag output [tables, batch, d] under
    the lane's plan (the DLRM forward's table vmap, without the rest)."""
    import jax

    from repro.layers.embedding import embedding_bag_fwd
    return jax.vmap(lambda tp, idx: embedding_bag_fwd(tp, idx, ctx))(
        params["tables"], bags)


def dlrm_phase(dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.dlrm import EXTRAS
    from repro.configs.registry import get_arch
    from repro.protect import ProtectionPlan, default_plan, protect
    from repro.serving import (FaultInjection, ServingEngine, TenantSpec,
                               dlrm_stream)

    ex = EXTRAS
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    check(limit is not None, "device reports bytes_limit")
    rows, why = fit_table_rows(ex, limit)
    ex = dataclasses.replace(ex, table_rows=rows)
    n_feat = ex.n_tables + 1
    log(f"geometry: {ex.n_tables} tables x {rows} rows x {ex.emb_dim} int8 "
        f"({ex.n_tables * rows * ex.emb_dim / 1e9:.2f} GB of table); "
        f"bottom MLP {(ex.n_dense,) + ex.bottom_mlp}, top MLP "
        f"{(ex.emb_dim + n_feat * (n_feat - 1) // 2,) + ex.top_mlp}, "
        f"pooling {ex.pooling}, batch {ex.batch}")
    log(f"rows: {why}")

    ref_plan = default_plan().with_rules(*ProtectionPlan.parse(
        "qgemm:scheme=xla,embedding_bag:scheme=xla").rules)
    tenants = [TenantSpec("pallas", default_plan()),
               TenantSpec("xla", dataclasses.replace(ref_plan, name="xla"))]
    t0 = time.perf_counter()
    engine = ServingEngine(get_arch("dlrm"), tenants, dlrm_extras=ex,
                           seed=SEED)
    jax.block_until_ready(engine.params)
    log(f"init_seconds: {time.perf_counter() - t0:.3f}")
    log(f"param_bytes: {tree_bytes(engine.params)}")

    n = N_REQUESTS
    base = dlrm_stream(n, tenants={"pallas": 1.0}, seed=SEED,
                       lookup_batch=ex.batch, table_rows=rows,
                       n_tables=ex.n_tables, max_pool=ex.pooling)
    twin = [dataclasses.replace(r, rid=r.rid + n, tenant="xla")
            for r in base]
    dense = jnp.asarray(base[0].payload["dense"])
    bags = jnp.asarray(base[0].payload["bags"])
    lanes = {next(iter(ln.tenants)): ln for ln in engine.lanes}
    mosaic, pooled = {}, {}
    for name, lane in sorted(lanes.items()):
        t0 = time.perf_counter()
        compiled = lane.forward_fn.lower(engine.params, dense, bags).compile()
        log(f"compile_seconds[{name}]: {time.perf_counter() - t0:.3f}")
        mosaic[name] = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        log(f"tpu_custom_calls[{name}]: {mosaic[name]}")
        fwd = protect(pooled_tables, lane.plan, compute_dtype=jnp.float32)
        pooled[name] = np.asarray(jax.jit(lambda p, b: fwd(p, b)[0])(
            engine.params, bags), np.float64)
    check(mosaic["pallas"] > 0, "the Pallas lane runs Mosaic kernels")
    got, ref = pooled["pallas"], pooled["xla"]
    check(got.shape == ref.shape == (ex.n_tables, ex.batch, ex.emb_dim)
          and np.isfinite(got).all() and np.isfinite(ref).all(),
          "finite pooled EmbeddingBag outputs in each lane")
    scale = np.max(np.abs(ref), axis=(1, 2), keepdims=True)
    worst = float(np.max(np.abs(got - ref) / np.maximum(scale, 1e-30)))
    log(f"pooled EB: max |pallas - xla| / table scale = {worst:.3e} "
        f"(tolerance {EB_RTOL:g})")
    check(worst <= EB_RTOL, "Pallas and XLA EmbeddingBags agree")
    t0 = time.perf_counter()
    engine.warmup(base[0])
    log(f"warmup_seconds: {time.perf_counter() - t0:.3f}")

    # clean pass: the same lookups through both lanes
    tel = engine.run(base + twin)
    recs = {r.rid: r for r in tel.requests}
    check(len(recs) == 2 * n and not any(r.aborted for r in recs.values()),
          f"all {2 * n} lookups served")
    flags = sum(ev.errors for ev in tel.steps)
    checks = sum(v for ev in tel.steps for k, v in ev.counters.items()
                 if k.endswith("_checks"))
    log(f"clean: {len(tel.steps)} steps, {checks} ABFT checks, "
        f"{flags} flags")
    check(checks > 0 and flags == 0, "clean pass raises zero ABFT flags")
    got = np.asarray([recs[r.rid].logits for r in base], np.float64)
    ref = np.asarray([recs[r.rid + n].logits for r in base], np.float64)
    check(got.shape == ref.shape == (n, ex.batch)
          and np.isfinite(got).all() and np.isfinite(ref).all(),
          f"{ex.batch} finite logits per request in each lane")
    scale = np.maximum(1.0, np.max(np.abs(ref), axis=1, keepdims=True))
    worst = float(np.max(np.abs(got - ref) / scale))
    log(f"logits: max |pallas - xla| / scale = {worst:.3e} "
        f"(tolerance {LOGIT_RTOL:g})")
    check(worst <= LOGIT_RTOL, "Pallas and XLA lanes agree")

    # one transient weight flip in the bottom MLP, on the Pallas lane.  A
    # table flip would be seen only by a lookup of its row (1 in 4M); an
    # MLP weight is read by every request.  Step 1 exists whatever the
    # admission batching: a step serves every queued lookup.
    engine.reset_state()
    tel = engine.run(base, inject=[FaultInjection(step=1, victim="bottom",
                                                  seed=SEED)])
    inj = tel.summary()["faults"]["injections"]
    for i in inj:
        log(f"injected {i['victim']} at step {i['step']}: "
            f"{'DETECTED' if i['detected'] else 'NOT detected'}"
            + (f" after {i['latency_steps']} step(s)"
               if i["detected"] else ""))
    check(len(inj) == 1 and inj[0]["detected"], "injected fault detected")

    stats = dev.memory_stats()
    log(f"peak_bytes_in_use: {stats['peak_bytes_in_use']} of bytes_limit "
        f"{stats['bytes_limit']}")


# --------------------------- four chips: checked psum ------------------------

def four_chip_programs(model, ctx, devs):
    """The programs of the ``--four-chips`` phase, over a ``("data",)`` mesh
    of ``devs``; the one-chip twins are vmaps over the replica axis, whose
    psum is a reduction over the mapped axis.

    -> (mesh, step4, step1, grads4, psum4, psum1):

    - ``step4(state, comm, batch)``: the repo's cross-chip train step,
      :func:`make_train_step_deferred`, one checked int8 psum per step;
    - ``step1``: the same steps from :func:`make_train_step` on one chip;
    - ``grads4(params, batch)``: each replica's gradient, on its own chip;
    - ``psum4(grads, flip)`` and ``psum1(grads, flip)``: each replica's
      gradient compressed as at step 0 (no residual) and summed by
      :func:`checked_psum`; with ``flip`` one bit of replica 0's payload
      flips before the collective.  -> (summed payload, scale sum, errors).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.launch.steps import make_train_step, make_train_step_deferred
    from repro.runtime.compression import (CompressionState, checked_psum,
                                           compress_grads)
    from repro.sharding import shard_map

    mesh = Mesh(np.asarray(devs), ("data",))
    hp = dict(peak_lr=LR, warmup=0, total_steps=100 * STEPS)
    unstack = lambda t: jax.tree.map(lambda x: x[0], t)    # noqa: E731
    stack = lambda t: jax.tree.map(lambda x: x[None], t)   # noqa: E731

    # state and residuals are donated: the one-chip program holds four
    # replicas' residuals, and an undonated copy of them would not fit
    step4 = jax.jit(make_train_step_deferred(model, ctx, mesh, **hp),
                    donate_argnums=(0, 1))
    step = make_train_step(model, ctx, compress=True, axis_name="data",
                           n_replicas=len(devs), **hp)

    def local_step(state, comm, batch):
        new, metrics = step({**state, "comm": comm}, batch)
        comm = new.pop("comm")
        return new, comm, {
            "loss_final": jax.lax.pmean(metrics["loss_final"], "data"),
            "comm/errors": metrics["comm/errors"]}

    step1 = jax.jit(jax.vmap(local_step, in_axes=(None, 0, 0),
                             out_axes=(None, 0, None), axis_name="data"),
                    donate_argnums=(0, 1))

    def replica_grads(params, batch):
        return jax.grad(lambda p: model.loss(p, batch, ctx),
                        has_aux=True)(params)[0]

    grads4 = jax.jit(shard_map(
        lambda p, b: stack(replica_grads(p, unstack(b))), mesh=mesh,
        in_specs=(P(), P("data")), out_specs=P("data")))

    def collective(grads, flip):
        no_residual = CompressionState(jax.tree.map(
            lambda g: jnp.zeros((), jnp.float32), grads))
        payload, _ = compress_grads(grads, no_residual)
        leaves, treedef = jax.tree.flatten(payload["q"])
        big = max(range(len(leaves)), key=lambda i: leaves[i].size)
        q = leaves[big].reshape(-1)
        hit = flip & (jax.lax.axis_index("data") == 0)
        q = q.at[0].set(jnp.where(hit, q[0] ^ jnp.int8(64), q[0]))
        leaves[big] = q.reshape(leaves[big].shape)
        return checked_psum({**payload, "q": jax.tree.unflatten(treedef,
                                                                leaves)},
                            "data")

    psum4 = jax.jit(shard_map(
        lambda g, f: collective(unstack(g), f), mesh=mesh,
        in_specs=(P("data"), P()), out_specs=(P(), P(), P())))
    psum1 = jax.jit(jax.vmap(collective, in_axes=(0, None), out_axes=None,
                             axis_name="data"))
    return mesh, step4, step1, grads4, psum4, psum1


def four_chip_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.campaign.executor import resolve_device_count
    from repro.configs.base import ShapeConfig
    from repro.configs.registry import get_arch
    from repro.data import make_dataset
    from repro.launch.steps import init_train_state
    from repro.layers.common import Ctx
    from repro.models.base import build_model

    resolve_device_count(N_CHIPS, strict=True)
    devs = jax.devices()[:N_CHIPS]
    cfg = dataclasses.replace(get_arch("granite-moe-3b-a800m"),
                              n_layers=LAYERS, train_accum=1)
    model = build_model(cfg, max_pos=cfg.attn_chunk)
    ctx = Ctx(quant=False, compute_dtype=jnp.float32)
    log(f"model: {cfg.name} at d_model {cfg.d_model}, {cfg.n_experts} "
        f"experts x d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.n_layers} "
        f"layer(s); global batch {BATCH} x {SEQ} tokens")
    mesh, step4, step1, grads4, psum4, psum1 = four_chip_programs(
        model, ctx, devs)

    # the initial state waits on the host, off both runs' chips
    state0 = jax.device_get(init_train_state(model, jax.random.key(SEED),
                                             compress=True))
    comm0 = jax.tree.map(lambda x: np.broadcast_to(x, (N_CHIPS,) + x.shape),
                         state0.pop("comm"))
    params0 = state0["params"]
    n_params = sum(x.size for x in jax.tree.leaves(params0))
    log(f"params: {n_params / 1e9:.3f} B")
    data = make_dataset(cfg, ShapeConfig("chip", "train", SEQ, BATCH))
    batches = [jax.tree.map(jnp.asarray, data.batch_at(s))
               for s in range(STEPS)]
    split = lambda b: jax.tree.map(                        # noqa: E731
        lambda x: x.reshape((N_CHIPS, x.shape[0] // N_CHIPS) + x.shape[1:]),
        b)
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("data"))

    # --- the collective alone, on the same per-replica gradients
    grads = grads4(jax.device_put(params0, repl),
                   jax.device_put(split(batches[0]), shard))
    check(all(len(x.sharding.device_set) == N_CHIPS
              for x in jax.tree.leaves(grads)),
          "each replica's gradient is on its own chip")
    summed4, scales4, errs4 = jax.device_get(psum4(grads, False))
    flipped = int(psum4(grads, True)[2])
    log(f"collective: clean errors {int(errs4)}, one flipped shard payload "
        f"-> errors {flipped}")
    check(int(errs4) == 0, "clean checked psum raises no error")
    check(flipped > 0, "flipped shard payload detected after psum")
    grads = jax.device_get(grads)

    def on_one_chip(replica_grads):
        out = psum1(jax.device_put(replica_grads, devs[0]), False)
        return jax.device_get(out)

    summed1, scales1, errs1 = on_one_chip(grads)
    check(int(errs1) == 0, "clean one-chip checked psum raises no error")
    # the control: replica 0 contributes replica 1's gradient
    summed_w, _, _ = on_one_chip(jax.tree.map(
        lambda x: np.concatenate([x[1:2], x[1:]]), grads))
    del grads

    def payload_gap(a, b):
        """-> (elements that differ, largest difference) of two int32
        payload sums."""
        d = [np.abs(x.astype(np.int64) - y) for x, y in
             zip(jax.tree.leaves(a), jax.tree.leaves(b))]
        return (sum(int(np.count_nonzero(x)) for x in d),
                max(int(x.max()) for x in d))

    scale_gap = max(float(abs(x - y) / abs(y)) for x, y in
                    zip(jax.tree.leaves(scales4), jax.tree.leaves(scales1)))
    for name, got in (("4-chip", summed4), ("wrong-shard", summed_w)):
        n_diff, big = payload_gap(got, summed1)
        log(f"payload sum {name} vs 1-chip: {n_diff} of {n_params} "
            f"elements differ, largest by {big}")
        if name == "4-chip":
            check(n_diff == 0, "4-chip payload sum equals the 1-chip one")
        else:
            check(n_diff > 0, "the comparison sees one shard wrong")
    log(f"scale sum 4-chip vs 1-chip: max relative gap {scale_gap:.3e} "
        f"(tolerance {SCALE_RTOL:g})")
    check(scale_gap <= SCALE_RTOL, "4-chip scale sums agree with 1-chip")
    del summed4, summed1, summed_w

    # --- training: the repo's shard_map step against make_train_step
    state = jax.device_put(state0, repl)
    comm = jax.device_put(comm0, shard)
    t0 = time.perf_counter()
    for s, b in enumerate(batches):
        state, comm, m = step4(state, comm, jax.device_put(b, shard))
        log(f"4-chip step {s}: loss {float(m['loss_final']):.6f} "
            f"comm/errors {int(m['comm/errors'])}")
        check(int(m["comm/errors"]) == 0, f"clean step {s}: comm/errors 0")
    jax.block_until_ready(state)
    log(f"4-chip seconds (compile included): {time.perf_counter() - t0:.3f}")
    check(all(len(x.sharding.device_set) == N_CHIPS
              for x in jax.tree.leaves((state, comm))),
          "the 4-chip state spans all four chips")
    params4 = jax.device_get(state["params"])
    del state, comm

    def run1(name, replica_batches):
        """The same steps on ONE chip -> its parameters."""
        state = jax.device_put(state0, devs[0])
        comm = jax.device_put(comm0, devs[0])
        t0 = time.perf_counter()
        for s, b in enumerate(replica_batches):
            state, comm, m = step1(state, comm, jax.device_put(b, devs[0]))
            log(f"{name} step {s}: loss {float(m['loss_final']):.6f} "
                f"comm/errors {int(m['comm/errors'])}")
            check(int(m["comm/errors"]) == 0,
                  f"{name} step {s}: comm/errors 0")
        log(f"{name} seconds (compile included): "
            f"{time.perf_counter() - t0:.3f}")
        return jax.device_get(state["params"])

    params1 = run1("1-chip", [split(b) for b in batches])
    # the control: replica 0 trains on replica 1's batch — one shard of
    # the all-reduce wrong, everything else as in the reference
    wrong = [jax.tree.map(lambda x: x.at[0].set(x[1]), split(b))
             for b in batches]
    params_w = run1("1-chip, one shard's batch wrong", wrong)

    def diff(a, b):
        """-> (L2 norm of a - b, share of elements apart by > LR / 2)."""
        sq, apart, n = 0.0, 0, 0
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            d = np.asarray(x, np.float64) - np.asarray(y, np.float64)
            sq += float(np.sum(d * d))
            apart += int(np.count_nonzero(np.abs(d) > LR / 2))
            n += d.size
        return math.sqrt(sq), apart / n

    moved, _ = diff(params1, params0)
    check(moved > 0, "the steps moved the parameters")
    rel = {}
    for name, got in (("4-chip", params4), ("wrong-shard", params_w)):
        dist, share = diff(got, params1)
        rel[name] = dist / moved
        log(f"params {name} vs 1-chip: |p - p1| / |p1 - p0| = "
            f"{rel[name]:.3e}, share of elements apart by > lr/2 = "
            f"{share:.3e}")
    check(rel["4-chip"] * PARAM_MARGIN <= rel["wrong-shard"],
          f"4-chip parameters agree with 1-chip: {PARAM_MARGIN}x closer "
          f"than with one shard's batch wrong")


# ------------------------------------ main -----------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip checked gradient all-reduce")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    dev = devs[0]
    log(f"device_kind: {dev.device_kind} x {len(devs)}")
    if args.four_chips:
        with jax.default_matmul_precision("highest"):
            four_chip_phase()
    else:
        dlrm_phase(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
