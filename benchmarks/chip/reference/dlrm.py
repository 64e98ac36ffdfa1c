"""Plain reference of the served DLRM, in float32, and its int4 control.

It imports nothing of the program and takes nothing the program made.  It
draws the same weights from the seed that the program's initialiser
draws, by the same recipe (one key split three ways: bottom MLP, top MLP,
tables; per linear a key for the int8 weight and one for its per-column
scale, biases zero; per table keys for the int8 rows, the per-row scale
α and the per-row offset β), and computes the DLRM's mathematics on them:

    bottom = relu(... relu(dense @ W_1) ... @ W_n)       (relu on the last)
    pooled[t, b] = Σ_slot (α_r · q_r + β_r)               r = bags[t, b, slot]
    z = [bottom, upper triangle of the Gram matrix of [bottom, pooled...]]
    logit = top MLP(z)                                    (no relu on the last)

with ``W = q · α`` (int8 values times their column scale), all in float32
at ``highest`` matmul precision.  The program quantizes activations to
int8 and computes in bfloat16 where this does not; the gap between the two
is what the benchmark compares.

``bits=4`` gives the control: the same computation with every int8
quantity of the model (weight columns, table rows, and each GEMM's input
rows) requantized to 4 bits, the nearest precision below the int8 the
configuration states.

Tables are generated one at a time and only the rows a sample reads are
kept, so the reference fits beside nothing else on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TABLE_ALPHA = (5e-3, 2e-2)
TABLE_BETA = (-0.1, 0.1)
WEIGHT_ALPHA = (1e-3, 2e-3)


def inter_dim(cfg: dict) -> int:
    f = int(cfg["n_tables"]) + 1
    return int(cfg["emb_dim"]) + f * (f - 1) // 2


def _mlp(key, dims):
    out = []
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        kw, ka = jax.random.split(k)
        w = jax.random.randint(kw, (dims[i], dims[i + 1]), -127, 128,
                               jnp.int8)
        a = jax.random.uniform(ka, (dims[i + 1],), jnp.float32,
                               *WEIGHT_ALPHA)
        out.append((w, a))
    return out


def keys(seed: int):
    """(bottom key, top key, per-table keys) of the model drawn from
    ``seed``."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    return k1, k2, k3


def mlp_weights(seed: int, cfg: dict):
    """-> (bottom, top): lists of (int8 weight [in, out], f32 scale [out])."""
    k1, k2, _ = keys(seed)
    bottom = _mlp(k1, (int(cfg["n_dense"]),) + tuple(cfg["bottom_mlp"]))
    top = _mlp(k2, (inter_dim(cfg),) + tuple(cfg["top_mlp"]))
    return bottom, top


@functools.partial(jax.jit, static_argnums=(2, 3))
def _table_rows(key, idx, rows: int, d: int):
    ka, kb, kc = jax.random.split(key, 3)
    q = jax.random.randint(ka, (rows, d), -127, 128, jnp.int8)
    a = jax.random.uniform(kb, (rows,), jnp.float32, *TABLE_ALPHA)
    b = jax.random.uniform(kc, (rows,), jnp.float32, *TABLE_BETA)
    return q[idx], a[idx], b[idx]


def quant_rows_sym(q, bits: int):
    """Requantize int rows (last axis) symmetrically to ``bits``; ->
    float values on the original integer scale."""
    hi = 2 ** (bits - 1) - 1
    q = q.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(q), axis=-1, keepdims=True), 1.0) / hi
    return jnp.clip(jnp.round(q / s), -hi - 1, hi) * s


def quant_act(x, bits: int):
    """Per-row asymmetric quantization of activations to ``bits``; ->
    dequantized values."""
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    xmin = jnp.min(x, axis=-1, keepdims=True)
    xmax = jnp.max(x, axis=-1, keepdims=True)
    a = jnp.maximum(xmax - xmin, 1e-12) / (hi - lo)
    b = xmin - lo * a
    return a * jnp.clip(jnp.round((x - b) / a), lo, hi) + b


def pooled(seed: int, cfg: dict, bags: np.ndarray, bits=None) -> np.ndarray:
    """Every table's pooled output for ``bags`` [n, T, B, pool];
    -> f32 [n, T, B, d]."""
    n, t_n, b_n, pool = bags.shape
    rows, d = int(cfg["table_rows"]), int(cfg["emb_dim"])
    _, _, k3 = keys(seed)
    tkeys = jax.random.split(k3, t_n)
    out = np.zeros((n, t_n, b_n, d), np.float32)
    for t in range(t_n):
        q, a, b = _table_rows(tkeys[t], jnp.asarray(bags[:, t].reshape(-1)),
                              rows, d)
        v = q.astype(jnp.float32) if bits is None else quant_rows_sym(q, bits)
        row = a[:, None] * v + b[:, None]
        out[:, t] = np.asarray(row).reshape(n, b_n, pool, d).sum(axis=2)
    return out


def _linear(x, w, a, bits):
    if bits is None:
        return x @ (w.astype(jnp.float32) * a)
    wq = quant_rows_sym(w.T, bits).T               # per output column
    return quant_act(x, bits) @ (wq * a)


@functools.partial(jax.jit, static_argnames=("bits",))
def _forward(dense, pooled_, bottom, top, bits=None):
    x = dense.astype(jnp.float32)
    for w, a in bottom:
        x = jax.nn.relu(_linear(x, w, a, bits))
    f = jnp.concatenate([x[None], pooled_], axis=0)          # [F, B, e]
    f = f.transpose(1, 0, 2)                              # [B, F, e]
    gram = jnp.einsum("bfe,bge->bfg", f, f)
    iu = np.triu_indices(f.shape[1], k=1)
    z = jnp.concatenate([x, gram[:, iu[0], iu[1]]], axis=-1)
    for i, (w, a) in enumerate(top):
        z = _linear(z, w, a, bits)
        if i < len(top) - 1:
            z = jax.nn.relu(z)
    return z[:, 0]


def logits(seed: int, cfg: dict, dense: np.ndarray, bags: np.ndarray,
           bits=None) -> np.ndarray:
    """Reference logits [n, B] for n requests (dense [n, B, n_dense], bags
    [n, T, B, pool]); ``bits=4`` gives the control."""
    with jax.default_matmul_precision("highest"):
        bottom, top = mlp_weights(seed, cfg)
        pool = pooled(seed, cfg, bags, bits)
        return np.stack([np.asarray(_forward(jnp.asarray(dense[i]),
                                             jnp.asarray(pool[i]), bottom,
                                             top, bits=bits))
                         for i in range(dense.shape[0])])
