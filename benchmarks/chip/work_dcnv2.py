"""The work a DLRMv2 (DLRM-DCNv2) request needs, counted from the
configuration's shapes.

As in ``work.py``, these counts are of the algorithm, not of any
implementation: padding and the extra rows a kernel fetches do not count.
DLRMv2 differs from the DLRMs there in two ways that change the counts:
each table has its own pool, and a low-rank cross network (two GEMMs a
layer) replaces the dot interaction.
"""
from __future__ import annotations


def gemm_shapes(cfg: dict, batch: int) -> list:
    """(m, k, n) of every int8 GEMM of one request: the bottom MLP, each
    cross layer's V (x_0 width -> rank) and W (rank -> x_0 width), the top
    MLP."""
    width = (int(cfg["n_tables"]) + 1) * int(cfg["emb_dim"])
    rank = int(cfg["cross_rank"])
    dims_b = [int(cfg["n_dense"])] + [int(x) for x in cfg["bottom_mlp"]]
    dims_t = [width] + [int(x) for x in cfg["top_mlp"]]
    cross = [(width, rank), (rank, width)] * int(cfg["cross_layers"])
    mlp = lambda dims: [(dims[i], dims[i + 1])              # noqa: E731
                        for i in range(len(dims) - 1)]
    return [(batch, k, n) for k, n in mlp(dims_b) + cross + mlp(dims_t)]


def int8_ops(cfg: dict, batch: int) -> int:
    """2 m k n over every int8 GEMM of one request."""
    return sum(2 * m * k * n for m, k, n in gemm_shapes(cfg, batch))


def eb_slots(cfg: dict, batch: int) -> int:
    """Bag positions of one request: each table's pool, once a sample."""
    return batch * sum(int(p) for p in cfg["pooling"])


def eb_bytes(cfg: dict, batch: int) -> int:
    """Bytes one request's EmbeddingBags need: each looked-up row (d int8)
    with its α and β (f32 each), once a slot; and each pooled output (d
    f32) with its row sum (f32)."""
    d = int(cfg["emb_dim"])
    return eb_slots(cfg, batch) * (d + 8) \
        + int(cfg["n_tables"]) * batch * (4 * d + 4)
