"""Linear layers: bf16 (training) and int8+ABFT (the paper's serving path).

The quantized linear runs Fig. 1 end to end:
  dynamic per-row activation quant (signed int8)  ->  int8 GEMM against the
  packed, checksum-encoded weight  ->  Eq. (3b) verify on the int32 C_temp
  (BEFORE requantization, §IV-B)  ->  rank-1 dequant + bias -> bf16.

Weights are packed once at init/conversion (amortized encoding, §IV-A1).
All verification goes through :func:`repro.protect.protected_call` — the
plan in ``ctx`` decides scheme (Pallas kernel or XLA reference; by
default Pallas exactly on a TPU), policy (log / recompute / correct /
abort), and on/off per call site ``name``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import policy
from repro.kernels import ops as kops
from repro.layers.common import Ctx
from repro.protect import ops as pops
from repro.protect.runtime import protected_call, rule_for
from repro.sharding import LogicalParam, param


# ----------------------------- bf16 linear ---------------------------------

def init_linear(key, d_in: int, d_out: int,
                axes: Tuple[str, str] = ("embed", "mlp"),
                dtype=jnp.float32, bias: bool = True,
                scale: Optional[float] = None):
    scale = (d_in ** -0.5) if scale is None else scale
    p = {"w": param(key, (d_in, d_out), axes, dtype, scale=scale)}
    if bias:
        p["b"] = LogicalParam(jnp.zeros((d_out,), dtype), (axes[1],))
    return p


def linear(p, x, ctx: Ctx, name: str = ""):
    """bf16 linear, optional float-ABFT (beyond paper) on the 2D GEMM."""
    w = p["w"].astype(ctx.compute_dtype)
    if rule_for(ctx, "float_gemm", name).enabled:
        m_shape = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        c, report = protected_call("float_gemm", (w, None), x2, ctx=ctx,
                                   name=name)
        y = c.astype(ctx.compute_dtype).reshape(*m_shape, w.shape[-1])
    else:
        y = jnp.dot(x.astype(ctx.compute_dtype), w,
                    preferred_element_type=ctx.compute_dtype)
        report = policy.empty_report()
    if "b" in p:
        y = y + p["b"].astype(ctx.compute_dtype)
    return y, report


# --------------------------- int8 ABFT linear ------------------------------

def init_qlinear(key, d_in: int, d_out: int,
                 axes: Tuple[str, str] = ("embed", "mlp"),
                 bias: bool = True):
    """Random-int8 quantized weight, packed with a consistent checksum.

    Real deployments convert from trained bf16 weights via
    :func:`quantize_linear`; random init keeps dry-run/eval_shape pure.
    """
    k1, k2 = jax.random.split(key)
    w_q = jax.random.randint(k1, (d_in, d_out), -127, 128, jnp.int8)
    packed = pops.QGEMM.encode(w_q)                     # [d_in, d_out+128]
    alpha = jax.random.uniform(k2, (d_out,), jnp.float32, 1e-3, 2e-3)
    colsum = pops.QGEMM.dequant_colsum(w_q)
    p = {
        "w_packed": LogicalParam(packed, (axes[0], axes[1])),
        "alpha": LogicalParam(alpha, (axes[1],)),
        "colsum": LogicalParam(colsum, (axes[1],)),
    }
    if bias:
        p["b"] = LogicalParam(jnp.zeros((d_out,), jnp.float32), (axes[1],))
    return p


def quantize_linear(p_f32, axes: Tuple[str, str] = ("embed", "mlp")):
    """Convert a trained bf16/f32 linear into the packed ABFT form."""
    from repro.quant import quantize_channels
    w = p_f32["w"].value if isinstance(p_f32["w"], LogicalParam) else p_f32["w"]
    q = quantize_channels(jnp.asarray(w, jnp.float32))
    packed = pops.QGEMM.encode(q.values)
    colsum = pops.QGEMM.dequant_colsum(q.values)
    out = {
        "w_packed": LogicalParam(packed, (axes[0], axes[1])),
        "alpha": LogicalParam(q.alpha, (axes[1],)),
        "colsum": LogicalParam(colsum, (axes[1],)),
    }
    if "b" in p_f32:
        b = p_f32["b"].value if isinstance(p_f32["b"], LogicalParam) else p_f32["b"]
        out["b"] = LogicalParam(jnp.asarray(b, jnp.float32), (axes[1],))
    return out


def qlinear(p, x, ctx: Ctx, name: str = ""):
    """int8 ABFT linear: x [..., d_in] -> (y [..., d_out] bf16, report)."""
    packed = p["w_packed"]
    d_in = packed.shape[0]
    d_out = packed.shape[1] - pops.QGEMM.lane
    m_shape = x.shape[:-1]
    x2 = x.reshape(-1, d_in)

    # dynamic per-row signed-int8 quantization (kernels/quantize_rows)
    x_q, a_alpha, a_beta = kops.quantize_rows(x2)

    # the plan decides scheme + policy + on/off for this call site; a
    # correct-policy site also hands over the exact int32 column sums so
    # single weight flips are repairable, not just detectable (the f32
    # colsum is exact for any d_in the int8 path supports: |sum| < 2^24)
    rule = rule_for(ctx, "qgemm", name)
    encoded = packed
    if rule.enabled and rule.policy == "correct" and "colsum" in p:
        encoded = (packed, jnp.round(p["colsum"]).astype(jnp.int32))
    c, report = protected_call("qgemm", encoded, x_q, ctx=ctx, rule=rule,
                               name=name)

    # Requantization rank-1 algebra (Eq. 1 with symmetric B: beta_B = 0):
    #   y = alpha_A[i] * alpha_B[j] * C[i,j] + beta_A[i] * alpha_B[j] * colsum_B[j]
    w_alpha = p["alpha"]
    y = (a_alpha[:, None] * (c.astype(jnp.float32) * w_alpha[None, :])
         + a_beta[:, None] * (w_alpha * p["colsum"])[None, :])
    if "b" in p:
        y = y + p["b"][None, :]
    y = y.astype(ctx.compute_dtype).reshape(*m_shape, d_out)
    return y, report


def maybe_qlinear_init(key, d_in, d_out, axes, quant: bool,
                       dtype=jnp.float32, bias: bool = True):
    if quant:
        return init_qlinear(key, d_in, d_out, axes, bias=bias)
    return init_linear(key, d_in, d_out, axes, dtype=dtype, bias=bias)


def apply_linear(p, x, ctx: Ctx, name: str = ""):
    """Dispatch on parameter form (packed int8 vs float)."""
    if "w_packed" in p:
        return qlinear(p, x, ctx, name)
    return linear(p, x, ctx, name)
