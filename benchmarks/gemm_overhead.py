"""Fig. 5 reproduction: ABFT overhead for low-precision GEMM, 28 shapes.

Three variants per (m, n, k):
  * ``unprotected``  — plain int8 GEMM (paper baseline)
  * ``abft``         — packed-checksum GEMM + fused verify, weight encoding
                       amortized (the paper's serving configuration)
  * ``abft+encode``  — encoding on the critical path (un-amortized bound)

Reports
  * measured wall-clock overhead (CPU backend — indicative only),
  * **modelled TPU overhead**: extra flops and extra HBM bytes of the ABFT
    program over the unprotected program, from the trip-aware HLO cost
    model (launch.costs) on the compiled artifacts — the container-honest
    reproduction of Fig. 5's claim,
  * the paper's analytic overhead ``1/(2m) + 1/n + 1/(2k)`` (§IV-A1),
  * the **fused Pallas** implementation: raw interpret-mode wall-clock
    (kernel-body emulation on CPU — NOT comparable to the XLA wall
    columns) plus its modelled TPU traffic.  The fused kernel's HBM
    traffic is exactly the packed GEMM's (A + B' in, C + err out): the
    verify runs on tiles still in VMEM, so unlike ``abft`` — whose
    Eq. (3b) reduction re-reads the O(mn) product — the bytes column
    collapses to the checksum lanes + the err vector.  The twin program
    priced by launch.costs is the packed dot; the in-VMEM verify's flops
    (~3·m·n') are added analytically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.common import GEMM_SHAPES, Csv, modelled_cost, time_fn
import repro.core as core
from repro.core import LANE
from repro.kernels.abft_qgemm import abft_qgemm_pallas


@functools.partial(jax.jit, static_argnums=())
def _plain(a, b):
    return jax.lax.dot_general(a.astype(jnp.int32), b.astype(jnp.int32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


@jax.jit
def _abft_packed(a, b_packed):
    return core.abft_qgemm_packed(a, b_packed)


@jax.jit
def _abft_encode(a, b):
    return core.abft_qgemm(a, b)


def _abft_pallas(a, b_packed):
    # the fused kernel (already jitted with static args); interpret mode
    # off the TPU only
    return abft_qgemm_pallas(
        a, b_packed, interpret=jax.devices()[0].platform != "tpu")


@jax.jit
def _packed_dot(a, b_packed):
    """The fused kernel's HBM traffic twin: one dot over the full packed
    operand (reads A + B', writes C including the checksum lanes)."""
    return jax.lax.dot_general(a.astype(jnp.int32),
                               b_packed.astype(jnp.int32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def run(csv: Csv, *, quick: bool = False):
    shapes = GEMM_SHAPES[::4] if quick else GEMM_SHAPES
    key = jax.random.key(0)
    for m, n, k in shapes:
        ka, kb = jax.random.split(jax.random.fold_in(key, m * n * k))
        a = jax.random.randint(ka, (m, k), 0, 256, jnp.uint8)
        b = jax.random.randint(kb, (k, n), -127, 128, jnp.int8)
        b_packed = jax.jit(core.pack_encoded_b)(b)
        t0 = time_fn(_plain, a, b)
        t1 = time_fn(_abft_packed, a, b_packed)
        t2 = time_fn(_abft_encode, a, b)
        t3 = time_fn(_abft_pallas, a, b_packed, iters=3, min_time_s=0.05)
        c0 = modelled_cost(_plain, a, b)
        c1 = modelled_cost(_abft_packed, a, b_packed)
        dflops = c1["flops"] / max(c0["flops"], 1) - 1
        dbytes = c1["bytes"] / max(c0["bytes"], 1) - 1
        # fused kernel: twin dot traffic + err vector out; verify flops
        # (mod + rowsum add + compare per C element) happen in VMEM
        ct = modelled_cost(_packed_dot, a, b_packed)
        p_flops = ct["flops"] + 3 * m * (n + LANE)
        p_bytes = ct["bytes"] + 4 * m
        pflops = p_flops / max(c0["flops"], 1) - 1
        pbytes = p_bytes / max(c0["bytes"], 1) - 1
        analytic = 1 / (2 * m) + 1 / n + 1 / (2 * k)
        csv.row("gemm_overhead", f"{m}x{n}x{k}",
                f"{t0*1e6:.1f}", f"{t1*1e6:.1f}", f"{t2*1e6:.1f}",
                f"{(t1/t0-1)*100:.1f}%", f"{(t2/t0-1)*100:.1f}%",
                f"{dflops*100:.2f}%", f"{dbytes*100:.2f}%",
                f"{analytic*100:.2f}%",
                f"{t3*1e6:.1f}",
                f"{pflops*100:.2f}%", f"{pbytes*100:.2f}%")


def main(quick: bool = False):
    csv = Csv(["bench", "shape_mxnxk", "plain_us", "abft_us",
               "abft_encode_us", "overhead_amortized", "overhead_encode",
               "tpu_flops_overhead", "tpu_bytes_overhead",
               "analytic_overhead", "pallas_interp_us",
               "pallas_tpu_flops_overhead", "pallas_tpu_bytes_overhead"])
    run(csv, quick=quick)
    return csv


if __name__ == "__main__":
    main()
