"""Pallas TPU kernel: chunked matmul-form WKV6 forward.

The XLA chunked form (layers.rwkv6.wkv_chunked) already lands 123x on the
rwkv train cell, but XLA still materializes every per-chunk normalization
tensor to HBM (EXPERIMENTS §Perf hillclimb 1, iters 2-4).  This kernel is
the structural fix: ALL per-chunk tensors (cumulative log-decay, the three
normalized operands, the [C, C] score tile) live in VMEM/registers; HBM
traffic is exactly the r/k/v/lw input streams + the y output stream + the
state carried in VMEM across the whole sequence.

Grid: (B·H, n_chunks) — batch·head parallel, chunks sequential
("arbitrary") so the S scratch [dh, dh] carries across chunk steps.

Math is identical to layers.rwkv6.wkv_chunked (same f32 envelope:
chunk · |LOG_W_MIN| ≤ 80); the pure-jnp oracle is
layers.rwkv6.wkv_recurrent, asserted in tests/test_wkv_chunked.py.

TPU note: dh = 64 for the assigned rwkv6-1.6b; production would pad the
lane dim to 128 (the wrapper zero-pads — checksum-neutral like the qgemm
kernel's padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_ref, *, n_chunks: int, chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _load_state():
        s_ref[...] = s0_ref[0]

    r_ = r_ref[0, 0]                      # [C, dh] f32
    k_ = k_ref[0, 0]
    v_ = v_ref[0, 0]
    lw = lw_ref[0, 0]
    u = u_ref[0]                          # [dh]

    la = jnp.cumsum(lw, axis=0)           # [C, dh]
    la_prev = la - lw
    la_end = la[-1:, :]                   # [1, dh]

    rt = r_ * jnp.exp(la_prev)            # bounded ≤ |r|
    kin = k_ * jnp.exp(-la)               # ≤ e^{C·|lw_min|} (envelope)
    kst = k_ * jnp.exp(la_end - la)       # bounded ≤ |k|
    diag = jnp.sum(r_ * u[None, :] * k_, axis=1)          # [C]

    s_cur = s_ref[...]                    # [dh, dh] (key x value)
    y_inter = jnp.dot(rt, s_cur, preferred_element_type=jnp.float32)
    scores = jnp.dot(rt, kin.T, preferred_element_type=jnp.float32)
    mask = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            > jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    scores = jnp.where(mask, scores, 0.0)
    y = (y_inter + jnp.dot(scores, v_, preferred_element_type=jnp.float32)
         + diag[:, None] * v_)
    y_ref[0, 0] = y

    s_ref[...] = (jnp.exp(la_end[0])[:, None] * s_cur
                  + jnp.dot(kst.T, v_, preferred_element_type=jnp.float32))

    @pl.when(c == n_chunks - 1)
    def _store_state():
        sout_ref[0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_chunked_pallas(rh, kh, vh, lwh, u, state, *, chunk: int = 16,
                       interpret: bool = False):
    """rh/kh/vh/lwh [B,S,H,dh] f32, u [H,dh], state [B,H,dh,dh].

    Returns (ys [B,S,H,dh], new_state [B,H,dh,dh]) — drop-in for
    layers.rwkv6.wkv_chunked.
    """
    b, s, h, dh = rh.shape
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    bh = b * h

    def prep(x):   # [B,S,H,dh] -> [BH, n_chunks, C, dh]
        return (x.transpose(0, 2, 1, 3)
                .reshape(bh, n_chunks, chunk, dh))

    rc, kc, vc, lwc = map(prep, (rh, kh, vh, lwh))
    u_bh = jnp.broadcast_to(u[None], (b, h, dh)).reshape(bh, dh)
    s0 = state.reshape(bh, dh, dh).astype(jnp.float32)

    kernel = functools.partial(_kernel, n_chunks=n_chunks, chunk=chunk)
    ys, s_out = pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dh), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, dh), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, dh), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, dh), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, dh), lambda i, c: (i, 0)),
            pl.BlockSpec((1, dh, dh), lambda i, c: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dh), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, dh, dh), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_chunks, chunk, dh), jnp.float32),
            jax.ShapeDtypeStruct((bh, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rc, kc, vc, lwc, u_bh, s0)

    ys = (ys.reshape(b, h, s, dh).transpose(0, 2, 1, 3))
    return ys, s_out.reshape(b, h, dh, dh)
