"""Plain reference of DLRMv2 (DLRM-DCNv2), for comparison with the served
forward (:func:`repro.models.dlrm.dlrm_forward`, ``interaction="dcnv2"``).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
on the same int8 weights and table rows (the ``params`` that
:func:`repro.models.dlrm.init_dlrm` draws from a seed): no kernels, no
quantized activations, no checks.

    bottom = relu(... relu(dense W_1 + b_1) ... W_n + b_n)   (relu on the last)
    pooled_t[b] = Σ_slot α_r q_r + β_r       r = bags_t[b, slot], −1 skipped
    x_0 = [bottom, pooled_1, ..., pooled_T]
    x_{l+1} = x_0 ⊙ (W_l (V_l x_l) + b_l) + x_l
    logit = top MLP(x_L)                                (no relu on the last)

with W = q · α (int8 values times their column scale).  Departures from
torchrec's ``DLRM_DCN`` (MLPerf's reference): inference only, so the logit
is returned without the sigmoid; and a configuration that holds only a
chip's share of a table's rows (the benchmark's two-chip row split) is
given ids from that share only, in the program and here alike.

``bits=4`` gives the control: every int8 quantity (weight columns, table
rows, each GEMM's input rows) requantized to 4 bits, the precision below
the configuration's int8, which the comparison must tell apart.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.dlrm import DlrmExtras
from repro.layers.embedding import table_offsets


def quant_sym(q, bits: int):
    """Requantize integers symmetrically to ``bits`` along the last axis;
    -> float values on the original integer scale."""
    hi = 2 ** (bits - 1) - 1
    q = q.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(q), axis=-1, keepdims=True), 1.0) / hi
    return jnp.clip(jnp.round(q / s), -hi - 1, hi) * s


def quant_act(x, bits: int):
    """Per-row asymmetric quantization to ``bits``; -> dequantized values."""
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    xmin = jnp.min(x, axis=-1, keepdims=True)
    xmax = jnp.max(x, axis=-1, keepdims=True)
    a = jnp.maximum(xmax - xmin, 1e-12) / (hi - lo)
    b = xmin - lo * a
    return a * jnp.clip(jnp.round((x - b) / a), lo, hi) + b


def _linear(p, x, bits):
    n = p["alpha"].shape[0]
    q = p["w_packed"][:, :n]
    w = q.astype(jnp.float32) if bits is None else quant_sym(q.T, bits).T
    if bits is not None:
        x = quant_act(x, bits)
    y = x @ (w * p["alpha"])
    return y + p["b"] if "b" in p else y


def _pooled(tables, bags, ex: DlrmExtras, bits):
    """[B, Σ pool] table-local ids -> [B, T·d]: each table's bag sums."""
    offs, out, col = table_offsets(ex.rows), [], 0
    for t, pool in enumerate(ex.pools):
        ids = bags[:, col:col + pool]
        rows = jnp.where(ids >= 0, ids + offs[t], 0)
        q = tables["table"][rows]                          # [B, pool, d]
        v = q.astype(jnp.float32) if bits is None else quant_sym(q, bits)
        row = tables["alphas"][rows][..., None] * v \
            + tables["betas"][rows][..., None]
        out.append(jnp.sum(jnp.where((ids >= 0)[..., None], row, 0.0),
                           axis=1))
        col += pool
    return jnp.concatenate(out, axis=-1)


@functools.partial(jax.jit, static_argnames=("ex", "bits"))
def dlrm_reference(params, dense, bags, ex: DlrmExtras,
                   bits=None) -> jax.Array:
    """Reference logits [B] of DLRMv2 for ``dense`` [B, n_dense] and
    ``bags`` [B, Σ pooling] (the served layout); ``bits=4`` gives the
    control."""
    if ex.interaction != "dcnv2" or not ex.ragged:
        raise ValueError("the reference is DLRMv2's: interaction 'dcnv2' "
                         "over tables held one after the other")
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(dense, jnp.float32)
        for p in params["bottom"]:
            x = jax.nn.relu(_linear(p, x, bits))
        x0 = jnp.concatenate(
            [x, _pooled(params["tables"], jnp.asarray(bags), ex, bits)],
            axis=-1)
        x, cross = x0, params["cross"]
        for i in range(cross["v"]["w_packed"].shape[0]):
            p = jax.tree.map(lambda a: a[i], cross)
            x = x0 * _linear(p["w"], _linear(p["v"], x, bits), bits) + x
        for i, p in enumerate(params["top"]):
            x = _linear(p, x, bits)
            if i < len(params["top"]) - 1:
                x = jax.nn.relu(x)
        return x[:, 0]
