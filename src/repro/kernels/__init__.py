"""Pallas TPU kernels for the paper's two hot-spot operators.

The paper's contribution is precisely a kernel-level one (FBGEMM-fused ABFT);
we provide the TPU-native equivalents:

- :mod:`repro.kernels.abft_qgemm`        — int8 GEMM with lane-aligned
  checksum block and verification fused in the epilogue (zero extra HBM
  traffic for the verify pass — beyond the paper's cache-resident re-read).
- :mod:`repro.kernels.abft_embeddingbag` — gather + bag-sum from a ring of
  in-flight row DMAs (scalar-prefetched indices, many bags a grid step),
  with the Eq. 5 row-sum accumulated in the same pass.
- :mod:`repro.kernels.quantize_rows`     — per-row dynamic activation
  quantization feeding the GEMM.
- :mod:`repro.kernels.wkv6_chunked`      — chunked matmul-form WKV6 with
  the state resident in VMEM across the sequence (EXPERIMENTS §Perf
  hillclimb 1, iteration 5).

``ref.py`` holds the pure-jnp oracles; ``ops.py`` the jit'd public wrappers
(the Pallas kernel on a TPU, the XLA reference elsewhere, unless told).
"""
