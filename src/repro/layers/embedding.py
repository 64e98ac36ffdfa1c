"""Token embeddings: bf16 gather (training) and quantized EB + ABFT (serving).

A token lookup is an EmbeddingBag with pooling size 1 (paper §III-C); the
serving path therefore verifies Eq. (5) per token batch.  DLRM's multi-hot
bags use the same code with pool > 1 and optional per-index weights.
Verification routes through :func:`repro.protect.protected_call`
(op kind ``embedding_bag``) so the plan controls on/off, policy, and the
Eq. (5) ``rel_bound`` per call site.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import policy
from repro.layers.common import Ctx
from repro.protect import ops as pops
from repro.protect.runtime import protected_call
from repro.sharding import LogicalParam, param


def init_embed(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": param(key, (vocab, d), ("vocab", "embed"), dtype)}


def embed(p, tokens, ctx: Ctx):
    x = p["table"][tokens].astype(ctx.compute_dtype)
    return x, policy.empty_report()


def init_qembed(key, vocab: int, d: int):
    """Quantized table (+ per-row alpha/beta) with precomputed row sums."""
    k1, k2, k3 = jax.random.split(key, 3)
    table = jax.random.randint(k1, (vocab, d), -127, 128, jnp.int8)
    alphas = jax.random.uniform(k2, (vocab,), jnp.float32, 5e-3, 2e-2)
    betas = jax.random.uniform(k3, (vocab,), jnp.float32, -0.1, 0.1)
    _, _, _, rowsums = pops.EMBEDDING_BAG.encode((table, alphas, betas))
    return {
        "table": LogicalParam(table, ("vocab", "embed")),
        "alphas": LogicalParam(alphas, ("vocab",)),
        "betas": LogicalParam(betas, ("vocab",)),
        "rowsums": LogicalParam(rowsums, ("vocab",)),
    }


def qembed(p, tokens, ctx: Ctx, name: str = "embed"):
    """tokens [...] int32 -> ([..., d] bf16, report). Pool size 1 EB-ABFT."""
    shape = tokens.shape
    bags = tokens.reshape(-1, 1)
    enc = (p["table"], p["alphas"], p["betas"], p["rowsums"])
    r, report = protected_call("embedding_bag", enc, bags, ctx=ctx,
                               name=name)
    d = p["table"].shape[-1]
    return r.astype(ctx.compute_dtype).reshape(*shape, d), report


def init_embedding_bag(key, rows: int, d: int):
    """DLRM-style multi-hot table (quantized, ABFT-ready)."""
    p = init_qembed(key, rows, d)
    p["table"] = LogicalParam(p["table"].value, ("table_rows", "embed"))
    p["alphas"] = LogicalParam(p["alphas"].value, ("table_rows",))
    p["betas"] = LogicalParam(p["betas"].value, ("table_rows",))
    p["rowsums"] = LogicalParam(p["rowsums"].value, ("table_rows",))
    return p


#: rows of a table drawn at once, at most: a concatenated table's set-up
#: holds one chunk's temporaries, not one table's (a 20M x 128 draw alone
#: takes 10 GB)
INIT_CHUNK = 1 << 16
#: the concatenated table's length is a multiple of this (one lane tile)
ROW_ALIGN = 128


def init_chunk(rows) -> int:
    """Rows a chunk of these tables holds: ``INIT_CHUNK``, or fewer where
    every table is smaller (a multiple of 8 all the same)."""
    return min(INIT_CHUNK, -(-max(rows) // 8) * 8)


def table_offsets(rows) -> tuple:
    """Each table's first row in the concatenated table, then the table's
    length: every table's rows, one chunk of slack (a table's last chunk is
    drawn and written whole; the next table's first chunk overwrites what
    passes its end), rounded up to ``ROW_ALIGN``."""
    offs = [0]
    for n in rows:
        offs.append(offs[-1] + int(n))
    offs[-1] = -(-(offs[-1] + init_chunk(rows)) // ROW_ALIGN) * ROW_ALIGN
    return tuple(offs)


def init_embedding_bags(key, rows, d: int):
    """Tables of unequal rows (DLRMv2's 3 to 20M), one after the other in
    one table [R, d] with per-row α, β and row sums; table t starts at
    ``table_offsets(rows)[t]``.  Table t is drawn from the t-th of T keys
    split from ``key``, ``init_chunk(rows)`` rows at a time, chunk c as
    :func:`init_qembed` draws a table of that many rows from
    ``fold_in(key_t, c)`` (the last chunk's rows past the table's end are
    not the table's).  Chunks are written in place, in order, by one loop."""
    offs, chunk = table_offsets(rows), init_chunk(rows)
    plan = jnp.asarray([(t, c, offs[t] + c * chunk)
                        for t, n in enumerate(rows)
                        for c in range(-(-int(n) // chunk))], jnp.int32)
    keys = jax.random.split(key, len(rows))

    def put(i, out):
        t, c, at = plan[i, 0], plan[i, 1], plan[i, 2]
        part = init_qembed(jax.random.fold_in(keys[t], c), chunk, d)
        return {name: jax.lax.dynamic_update_slice_in_dim(
            v, part[name].value, at, 0) for name, v in out.items()}

    out = jax.lax.fori_loop(0, len(plan), put, {
        "table": jnp.zeros((offs[-1], d), jnp.int8),
        "alphas": jnp.zeros((offs[-1],), jnp.float32),
        "betas": jnp.zeros((offs[-1],), jnp.float32),
        "rowsums": jnp.zeros((offs[-1],), jnp.int32)})
    return {name: LogicalParam(v, ("table_rows", "embed") if v.ndim == 2
                               else ("table_rows",))
            for name, v in out.items()}


def embedding_bag_fwd(p, indices, ctx: Ctx, weights=None,
                      name: str = "tables"):
    """indices [bags, pool] (−1 padded) -> ([bags, d], report).  A tuple
    of per-table [bags, pool_t] rows of a table that holds every table
    (:func:`init_embedding_bags`) gives [T, bags, d]."""
    enc = (p["table"], p["alphas"], p["betas"], p["rowsums"])
    r, report = protected_call("embedding_bag", enc, indices, weights,
                               ctx=ctx, name=name)
    return r.astype(ctx.compute_dtype), report


def apply_embed(p, tokens, ctx: Ctx):
    if "alphas" in p:
        return qembed(p, tokens, ctx)
    return embed(p, tokens, ctx)
