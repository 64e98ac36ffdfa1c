"""Plain reference of the served DLRMv2 (DLRM-DCNv2), in float32, and its
int4 control.

It imports nothing of the program and takes nothing the program made.  It
draws the same weights from the seed that the program's initialiser
draws, by the same recipe: one key split four ways (bottom MLP, top MLP,
tables, cross network); per linear a key split in two, for the int8
weight and for its per-column scale (as ``reference/dlrm.py``'s ``_mlp``
does), biases zero; per cross layer one of ``cross_layers`` keys split
from the cross key, split again for V (x_0 width -> rank) and W (rank ->
x_0 width); per table one of ``n_tables`` keys split from the tables key,
its rows drawn :func:`chunk_rows` at a time, chunk c from
``fold_in(table key, c)`` as ``reference/dlrm.py`` draws a whole table
(int8 rows, α, β), the table's rows being its chunks' rows in order.  It
computes:

    bottom = relu(... relu(dense @ W_1) ... @ W_n)        (relu on the last)
    pooled_t[b] = Σ_slot α_r · q_r + β_r       r = bags_t[b, slot]
    x_0 = [bottom, pooled_1, ..., pooled_26]              [B, 3456]
    x_{l+1} = x_0 ⊙ (x_l @ V_l @ W_l) + x_l               (3 layers)
    logit = top MLP(x_3)                                  (no relu on the last)

with ``W = q · α``, all in float32 at ``highest`` matmul precision.  The
program quantizes activations to int8 and computes in bfloat16 where this
does not.  Departures from torchrec's ``DLRM_DCN``: no sigmoid on the
logit (inference), and ids come only from the rows this chip holds of the
five 40M-row tables (the configuration's ``deployment``).

``bits=4`` gives the control: every int8 quantity (weight columns, table
rows, each GEMM's input rows) requantized to 4 bits.

Tables are drawn again one chunk at a time and only the rows a sample
reads are kept, so the reference fits beside nothing else on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import dlrm as ref

#: rows a table chunk holds, at most (the program's ``INIT_CHUNK``)
CHUNK = 1 << 16


def chunk_rows(cfg: dict) -> int:
    """Rows of one chunk: ``CHUNK``, or the largest table's rows rounded up
    to a multiple of 8 where that is fewer."""
    return min(CHUNK, -(-max(int(r) for r in cfg["table_rows"]) // 8) * 8)


def keys(seed: int):
    """(bottom, top, tables, cross) keys of the model drawn from ``seed``."""
    return jax.random.split(jax.random.key(seed), 4)


def weights(seed: int, cfg: dict):
    """-> (bottom, cross, top): bottom and top lists of (int8 weight [in,
    out], f32 scale [out]); cross a list of ((V, scale), (W, scale))."""
    kb, kt, _, kc = keys(seed)
    width = (int(cfg["n_tables"]) + 1) * int(cfg["emb_dim"])
    rank = int(cfg["cross_rank"])
    bottom = ref._mlp(kb, (int(cfg["n_dense"]),) + tuple(cfg["bottom_mlp"]))
    top = ref._mlp(kt, (width,) + tuple(cfg["top_mlp"]))
    cross = []
    for k in jax.random.split(kc, int(cfg["cross_layers"])):
        kv, kw = jax.random.split(k)
        cross.append((_linear_weights(kv, width, rank),
                      _linear_weights(kw, rank, width)))
    return bottom, cross, top


def _linear_weights(key, d_in: int, d_out: int):
    """(int8 weight [d_in, d_out], f32 scale [d_out]) from one key, as
    ``reference/dlrm.py``'s ``_mlp`` draws each of its layers."""
    kw, ka = jax.random.split(key)
    return (jax.random.randint(kw, (d_in, d_out), -127, 128, jnp.int8),
            jax.random.uniform(ka, (d_out,), jnp.float32, *ref.WEIGHT_ALPHA))


def _rows(tkey, ids: np.ndarray, chunk: int, d: int):
    """(int8 rows, α, β) of table rows ``ids`` (sorted, unique), drawn
    chunk by chunk; -> numpy arrays."""
    q = np.zeros((ids.size, d), np.int8)
    a = np.zeros(ids.size, np.float32)
    b = np.zeros(ids.size, np.float32)
    for c in np.unique(ids // chunk):
        lo, hi = np.searchsorted(ids, [c * chunk, (c + 1) * chunk])
        n = hi - lo
        # a power-of-two number of ids a call, so that few shapes compile
        pad = np.zeros(max(256, 1 << int(n - 1).bit_length()), np.int32)
        pad[:n] = ids[lo:hi] - c * chunk
        got = ref._table_rows(jax.random.fold_in(tkey, int(c)),
                              jnp.asarray(pad), chunk, d)
        q[lo:hi], a[lo:hi], b[lo:hi] = (np.asarray(x)[:n] for x in got)
    return q, a, b


def pooled(seed: int, cfg: dict, bags: np.ndarray, bits=None) -> np.ndarray:
    """Each table's pooled output for ``bags`` [n, B, Σ pooling] (table-local
    ids, table after table); -> f32 [n, B, T·d]."""
    n, b_n, _ = bags.shape
    d, chunk = int(cfg["emb_dim"]), chunk_rows(cfg)
    _, _, kt, _ = keys(seed)
    out, col = [], 0
    for tkey, pool in zip(jax.random.split(kt, int(cfg["n_tables"])),
                          cfg["pooling"]):
        ids = bags[:, :, col:col + int(pool)]
        col += int(pool)
        uniq, inv = np.unique(ids, return_inverse=True)
        q, a, b = _rows(tkey, uniq, chunk, d)
        v = q.astype(np.float32) if bits is None else np.asarray(
            ref.quant_rows_sym(jnp.asarray(q), bits))
        row = a[:, None] * v + b[:, None]                  # [uniq, d]
        out.append(row[inv.reshape(-1)].reshape(n, b_n, int(pool), d)
                   .sum(axis=2))
    return np.concatenate(out, axis=-1)


@functools.partial(jax.jit, static_argnames=("bits",))
def _forward(dense, pooled_, bottom, cross, top, bits=None):
    x = dense.astype(jnp.float32)
    for w, a in bottom:
        x = jax.nn.relu(ref._linear(x, w, a, bits))
    x0 = jnp.concatenate([x, pooled_], axis=-1)
    x = x0
    for (v, av), (w, aw) in cross:
        x = x0 * ref._linear(ref._linear(x, v, av, bits), w, aw, bits) + x
    for i, (w, a) in enumerate(top):
        x = ref._linear(x, w, a, bits)
        if i < len(top) - 1:
            x = jax.nn.relu(x)
    return x[:, 0]


def logits(seed: int, cfg: dict, dense: np.ndarray, bags: np.ndarray,
           bits=None) -> np.ndarray:
    """Reference logits [n, B] for n requests (dense [n, B, n_dense], bags
    [n, B, Σ pooling]); ``bits=4`` gives the control."""
    with jax.default_matmul_precision("highest"):
        bottom, cross, top = weights(seed, cfg)
        pool = pooled(seed, cfg, bags, bits)
        return np.stack([np.asarray(_forward(jnp.asarray(dense[i]),
                                             jnp.asarray(pool[i]), bottom,
                                             cross, top, bits=bits))
                         for i in range(dense.shape[0])])
