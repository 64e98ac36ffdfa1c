"""The paper's contribution: ABFT soft-error detection for low-precision ops.

- :mod:`repro.core.abft_gemm`      — Algorithm 1 (ABFT for quantized GEMM)
- :mod:`repro.core.abft_embedding` — Algorithm 2 (ABFT for quantized EmbeddingBag)
- :mod:`repro.core.abft_kvcache`   — beyond-paper quantized KV cache + checksums
- :mod:`repro.core.abft_float`     — beyond-paper float ABFT (training GEMMs)
- :mod:`repro.core.inject`         — bit-flip / value-replacement fault injection
- :mod:`repro.core.policy`         — FaultReport plumbing + detect->act policies
- :mod:`repro.core.checksum`       — pytree mod-checksums (checkpoints, collectives)

This package namespace is the stable import surface for the checksum
algebra.  Call sites (layers, kernels, benchmarks, examples) should import
from ``repro.core`` or — for protected execution — go through
:mod:`repro.protect`; the ``repro.core.abft_*`` module paths are an
implementation detail.
"""
from repro.core.abft_gemm import (
    LANE,
    MOD,
    AbftGemmOut,
    abft_qgemm,
    abft_qgemm_packed,
    correct_single_error,
    correct_weight_flip,
    detect_prob_b_bitflip,
    detect_prob_b_random,
    detect_prob_c_random,
    encode_activation_checksum,
    encode_weight_checksum,
    encode_weight_colsum,
    pack_encoded_b,
    verify_rows,
)
from repro.core.abft_embedding import (
    EB_REL_BOUND,
    AbftEbOut,
    abft_embedding_bag,
    eb_overhead_model,
    embedding_bag,
    table_rowsums,
    verify_bags,
)
from repro.core.abft_kvcache import (
    QuantKV,
    attend_quantized,
    dequantize_kv,
    quantize_kv_rows,
    update_kv_row,
    verify_kv,
)
from repro.core.abft_float import (
    FloatAbftOut,
    abft_gemm_f32,
    encode_weight_f32,
)
from repro.core.policy import (
    FaultReport,
    empty_report,
    merge_reports,
    op_report,
)

__all__ = [
    "MOD", "LANE", "AbftGemmOut",
    "encode_weight_checksum", "encode_activation_checksum",
    "abft_qgemm", "abft_qgemm_packed",
    "pack_encoded_b", "verify_rows", "correct_single_error",
    "encode_weight_colsum", "correct_weight_flip",
    "detect_prob_b_bitflip", "detect_prob_b_random", "detect_prob_c_random",
    "EB_REL_BOUND", "AbftEbOut", "table_rowsums", "embedding_bag",
    "abft_embedding_bag", "verify_bags", "eb_overhead_model",
    "QuantKV", "quantize_kv_rows", "dequantize_kv", "verify_kv",
    "update_kv_row", "attend_quantized",
    "FloatAbftOut", "encode_weight_f32", "abft_gemm_f32",
    "FaultReport", "op_report", "merge_reports", "empty_report",
]
