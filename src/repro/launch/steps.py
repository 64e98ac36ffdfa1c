"""Step-function builders: train (grad-accum + AdamW + clip), prefill,
decode.  These are what the launcher jits, the dry-run lowers, and the
examples drive.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import policy
from repro.layers.common import Ctx
from repro.models.base import Model
from repro.optim import adamw_update, clip_by_global_norm
from repro.optim.schedule import warmup_cosine


def make_train_step(model: Model, ctx: Ctx, *, accum: int = 1,
                    peak_lr: float = 3e-4, warmup: int = 100,
                    total_steps: int = 10000, max_grad_norm: float = 1.0,
                    compress: bool = False, axis_name=None,
                    n_replicas: int = 1):
    """(state, batch) -> (state, metrics).

    state = {"params", "opt", "step"}; batch leaves lead with the global
    batch dim; with accum > 1 the batch is split into microbatches and
    gradients accumulate in f32 (scan — live activations stay one
    microbatch wide).

    ``compress=True`` routes gradients through the int8 error-feedback
    compressed, mod-checksum verified reduction of
    :mod:`repro.runtime.compression` (state gains a ``"comm"``
    CompressionState — init via ``init_train_state(compress=True)``) and
    surfaces the verifier in ``metrics["comm/errors"]`` for the
    TrainLoop's detect->act policy.  ``axis_name=None`` is the
    single-device verify-only path; under shard_map/pmap pass the data
    axis and its size.
    """

    def loss_fn(params, mb):
        loss, (metrics, rep) = model.loss(params, mb, ctx)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        params = state["params"]
        if accum == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)

            def body(g_acc, mb):
                (l, m), g = grad_fn(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return g_acc, (l, m)

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            grads, (losses, metrics) = jax.lax.scan(body, g0, micro)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(
                lambda x: jnp.mean(x.astype(jnp.float32)), metrics)

        metrics = dict(metrics)
        new_state = {}
        if compress:
            from repro.runtime.compression import compressed_allreduce
            grads, new_comm, comm_errs = compressed_allreduce(
                grads, state["comm"], axis_name, n_replicas)
            metrics["comm/errors"] = comm_errs
            new_state["comm"] = new_comm

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = warmup_cosine(state["step"], peak=peak_lr, warmup=warmup,
                           total=total_steps)
        new_params, new_opt = adamw_update(grads, state["opt"], params, lr)
        new_state.update(params=new_params, opt=new_opt,
                         step=state["step"] + 1)
        metrics.update({"grad_norm": gnorm, "lr": lr, "loss_final": loss})
        return new_state, metrics

    return train_step


def make_train_step_deferred(model: Model, ctx: Ctx, mesh, *,
                             accum: int = 1, peak_lr: float = 3e-4,
                             warmup: int = 100, total_steps: int = 10000,
                             max_grad_norm: float = 1.0,
                             compress: bool = True,
                             data_axes=("data",)):
    """Deferred-gradient-sync train step (EXPERIMENTS §Perf hillclimb 2).

    The pjit step syncs gradients *inside every microbatch* (XLA places the
    data-axis all-reduce in the scan body — it cannot hoist it out of the
    while loop) and re-gathers FSDP weights per microbatch.  Here the data
    axis is manual (shard_map): each device accumulates LOCAL grads over
    its microbatches, then ONE gradient collective per step — int8
    error-feedback compressed and mod-checksum verified
    (runtime.compression: the paper's checksummed-operator philosophy
    applied to the wire).  Params are replicated over `data` (sharded over
    `model` by the auto axis) — for models whose optimizer state fits
    without ZeRO.

    Returns (state, comm, batch) -> (state, comm, metrics).  ``comm`` is the
    per-device error-feedback residual tree with a leading data-axis dim
    (``init_train_state(..., compress=True)["comm"]`` broadcast over the
    data axis); pass ``comm=None`` trees when ``compress=False``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.runtime.compression import (checked_psum, compress_grads,
                                           decompress_grads)
    from repro.runtime.compression import CompressionState

    def loss_fn(params, mb):
        loss, (metrics, rep) = model.loss(params, mb, ctx)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    n_data = 1
    for a in data_axes:
        n_data *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    axis = data_axes if len(data_axes) > 1 else data_axes[0]

    def local_grads(params, batch):
        """Grad accumulation over local microbatches — no collectives."""
        if accum == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return grads, loss, metrics
        micro = jax.tree.map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
            batch)

        def body(g_acc, mb):
            (l, m), g = grad_fn(params, mb)
            return jax.tree.map(
                lambda a_, b_: a_ + b_.astype(jnp.float32), g_acc, g), (l, m)

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        grads, (losses, metrics) = jax.lax.scan(body, g0, micro)
        grads = jax.tree.map(lambda g: g / accum, grads)
        metrics = jax.tree.map(
            lambda x: jnp.mean(x.astype(jnp.float32)), metrics)
        return grads, jnp.mean(losses), metrics

    def _reduce_metrics(metrics):
        def red(v):
            v = jnp.asarray(v)
            if jnp.issubdtype(v.dtype, jnp.integer):
                return jax.lax.psum(v, axis)
            return jax.lax.pmean(v.astype(jnp.float32), axis)
        return jax.tree.map(red, metrics)

    def step(state, comm, batch):
        params = state["params"]
        grads, loss, metrics = local_grads(params, batch)

        if compress:
            comm_local = CompressionState(
                error=jax.tree.map(lambda e: e[0], comm.error))
            payload, comm_local = compress_grads(grads, comm_local)
            summed, scale_sum, comm_errs = checked_psum(payload, axis)
            grads = decompress_grads(summed, scale_sum, n_data)
            comm = CompressionState(
                error=jax.tree.map(lambda e: e[None], comm_local.error))
            metrics = dict(metrics)
            metrics["comm/errors"] = comm_errs
        else:
            grads = jax.lax.pmean(grads, axis)

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = warmup_cosine(state["step"], peak=peak_lr, warmup=warmup,
                           total=total_steps)
        new_params, new_opt = adamw_update(grads, state["opt"], params, lr)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics)
        metrics.update({"grad_norm": gnorm, "lr": lr, "loss_final": loss})
        return new_state, comm, _reduce_metrics(metrics)

    from repro.sharding import shard_map
    return shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P(axis), P()),
        manual_axes=set(data_axes))


def make_prefill_step(model: Model, ctx: Ctx, cache_len: int):
    """(params, batch) -> (next_token [B], cache, metrics)."""

    def prefill_step(params, batch):
        logits, cache, rep = model.prefill(params, batch, ctx, cache_len)
        next_tok = jnp.argmax(
            logits[..., :model.cfg.vocab], axis=-1).astype(jnp.int32)
        return next_tok, cache, rep.as_metrics()

    return prefill_step


def make_decode_step(model: Model, ctx: Ctx):
    """(params, cache, tokens [B], pos [B]) -> (next [B], cache, metrics)."""

    def decode_step(params, cache, tokens, pos):
        logits, new_cache, rep = model.decode(params, cache, tokens, pos,
                                              ctx)
        next_tok = jnp.argmax(
            logits[..., :model.cfg.vocab], axis=-1).astype(jnp.int32)
        return next_tok, new_cache, rep.as_metrics()

    return decode_step


def init_train_state(model: Model, key, *, dtype=jnp.float32,
                     compress: bool = False):
    """Concrete state (examples / small runs).

    ``compress=True`` adds the ``"comm"`` error-feedback residual tree for
    ``make_train_step(compress=True)``."""
    from repro.optim import adamw_init
    from repro.sharding import values_of

    params = values_of(model.init(key, dtype=dtype))
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    if compress:
        from repro.runtime.compression import init_compression
        state["comm"] = init_compression(params)
    return state


def train_state_lp(model: Model, *, dtype=jnp.float32,
                   compress: bool = False):
    """LogicalParam tree of ShapeDtypeStructs for the full train state.

    Moments carry the parameter's logical axes (ZeRO falls out of the FSDP
    rules); non-trainable leaves (packed int8 weights, EB tables) get
    zero-size placeholders, matching optim.adamw_init.  ``compress=True``
    adds the f32 error-feedback residuals, sharded like their parameters.
    """
    from repro.sharding import LogicalParam, is_lp

    params_lp = jax.eval_shape(
        lambda: model.init(jax.random.key(0), dtype=dtype))

    def mom(p):
        v = p.value
        if jnp.issubdtype(v.dtype, jnp.floating):
            return LogicalParam(
                jax.ShapeDtypeStruct(v.shape, jnp.float32), p.axes)
        return LogicalParam(
            jax.ShapeDtypeStruct((0,), jnp.float32), (None,))

    m_lp = jax.tree.map(mom, params_lp, is_leaf=is_lp)
    scalar = LogicalParam(jax.ShapeDtypeStruct((), jnp.int32), ())
    state = {
        "params": params_lp,
        "opt": {"m": m_lp,
                "v": jax.tree.map(lambda x: x, m_lp, is_leaf=is_lp),
                "count": scalar},
        "step": scalar,
    }
    if compress:
        from repro.runtime.compression import CompressionState

        def residual(p):
            return LogicalParam(
                jax.ShapeDtypeStruct(p.value.shape, jnp.float32), p.axes)

        state["comm"] = CompressionState(
            error=jax.tree.map(residual, params_lp, is_leaf=is_lp))
    return state
