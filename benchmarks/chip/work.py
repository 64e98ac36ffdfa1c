"""The work a DLRM request needs, counted from the configuration's shapes.

These counts are of the algorithm, not of any implementation: padding,
re-layout copies and the extra rows a kernel fetches do not count.  They
feed the roofline shares and ``mfu``.
"""
from __future__ import annotations

import json
from pathlib import Path


def gemm_shapes(cfg: dict, batch: int) -> list:
    """(m, k, n) of every int8 GEMM of one request: bottom MLP, top MLP."""
    f = int(cfg["n_tables"]) + 1
    dims_b = [int(cfg["n_dense"])] + [int(x) for x in cfg["bottom_mlp"]]
    dims_t = ([int(cfg["emb_dim"]) + f * (f - 1) // 2]
              + [int(x) for x in cfg["top_mlp"]])
    return [(batch, dims[i], dims[i + 1]) for dims in (dims_b, dims_t)
            for i in range(len(dims) - 1)]


def qgemm_work(m: int, k: int, n: int) -> tuple:
    """Algorithm 1 on one GEMM: C' = A [B | B e], then a per-row check.

    -> (int8 ops, bytes): 2 m k (n + 1) ops; A (m k int8), B and its
    checksum column (k (n + 1) int8), C (m n int32) and the per-row flags
    (m int32)."""
    return 2 * m * k * (n + 1), m * k + k * (n + 1) + 4 * m * n + 4 * m


def eb_bytes(cfg: dict, batch: int, pool: int) -> int:
    """Bytes one request's EmbeddingBags need: each looked-up row (d int8)
    with its α and β (f32 each), ``pool`` of them in each of the request's
    bags, counted once per slot; and each pooled output (d f32) with its
    row sum (f32)."""
    t, d = int(cfg["n_tables"]), int(cfg["emb_dim"])
    return t * batch * (pool * (d + 8) + 4 * d + 4)


def model_ops(cfg: dict, batch: int) -> dict:
    """Operations of one request by the peak they run against: the int8
    GEMMs (2 m k n) against the int8 peak, the pairwise interaction (the
    upper triangle of the Gram matrix, 2 e per pair) against the bf16
    peak.  The EmbeddingBag's additions are bound by bytes and are in
    :func:`eb_bytes` instead."""
    f = int(cfg["n_tables"]) + 1
    e = int(cfg["emb_dim"])
    int8 = sum(2 * m * k * n for m, k, n in gemm_shapes(cfg, batch))
    return {"int8": int8, "bf16": batch * f * (f - 1) // 2 * 2 * e}


def load_peaks(path, device_kind: str) -> dict:
    """The peaks row for ``device_kind``; a device not in the table is an
    error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{Path(path).name}; have {sorted(table)}")
    return table[device_kind]


def least_seconds(ops: float, nbytes: float, peak_ops: float,
                  peaks: dict) -> float:
    """Roofline: the larger of compute time at peak and HBM time."""
    return max(ops / peak_ops, nbytes / float(peaks["hbm_bytes_per_s"]))
