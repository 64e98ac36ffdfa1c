"""The paper's own architecture: DLRM (quantized, ABFT-protected).

Bottom MLP over dense features, quantized embedding tables with multi-hot
EmbeddingBag lookups, a feature interaction, top MLP -> CTR logit.

:data:`EXTRAS` is the paper's EB evaluation (Table I): 26 tables of 4M
rows, pooling 100, batch 10, with the pairwise dot interaction of DLRMv1.
:data:`DCNV2` is MLPerf's DLRMv2 (DLRM-DCNv2): 26 tables of 3 to 40M rows,
each with its own multi-hot size, and a low-rank cross network in place of
the dot interaction.  The GEMM shapes of the detection benchmarks and
campaign follow Fig. 5 (``repro.campaign.spec.DLRM_GEMM_SHAPES``)."""
import dataclasses
from typing import Union

from repro.configs.base import ArchConfig

#: the pairwise dot products of DLRMv1, and DLRMv2's low-rank cross network
INTERACTIONS = ("dot", "dcnv2")


@dataclasses.dataclass(frozen=True)
class DlrmExtras:
    """DLRM shapes.  ``table_rows`` and ``pooling`` are one number for
    every table, or a tuple with one a table; with either a tuple the
    tables are held one after the other in one table and a request's bags
    are one ``[batch, Σ pooling]`` array (:attr:`ragged`).

    ``interaction="dcnv2"`` runs ``cross_layers`` layers of
    x_{l+1} = x_0 ⊙ (W_l (V_l x_l) + b_l) + x_l over x_0 = [bottom output,
    every pooled table] (V_l of rank ``cross_rank``)."""
    n_dense: int = 13
    bottom_mlp: tuple = (512, 256, 128)
    n_tables: int = 26
    table_rows: Union[int, tuple] = 4_000_000
    emb_dim: int = 128
    pooling: Union[int, tuple] = 100
    top_mlp: tuple = (1024, 1024, 512, 256, 1)
    batch: int = 10             # paper Table I batch size
    interaction: str = "dot"
    cross_layers: int = 0
    cross_rank: int = 0

    def __post_init__(self):
        if self.interaction not in INTERACTIONS:
            raise ValueError(f"unknown interaction {self.interaction!r}; "
                             f"have {INTERACTIONS}")
        for key in ("table_rows", "pooling"):
            v = getattr(self, key)
            if isinstance(v, tuple) and len(v) != self.n_tables:
                raise ValueError(f"{key} has {len(v)} entries for "
                                 f"{self.n_tables} tables")

    @property
    def ragged(self) -> bool:
        """Tables differ in rows or pool."""
        return isinstance(self.table_rows, tuple) or isinstance(
            self.pooling, tuple)

    @property
    def rows(self) -> tuple:
        """Rows of each table."""
        r = self.table_rows
        return r if isinstance(r, tuple) else (r,) * self.n_tables

    @property
    def pools(self) -> tuple:
        """Rows in each bag of each table."""
        p = self.pooling
        return p if isinstance(p, tuple) else (p,) * self.n_tables

    @property
    def inter_dim(self) -> int:
        """Width of the interaction's output, the top MLP's input."""
        f = self.n_tables + 1
        if self.interaction == "dcnv2":
            return f * self.emb_dim
        return self.emb_dim + f * (f - 1) // 2


CONFIG = ArchConfig(
    name="dlrm",
    family="dlrm",
    n_layers=0,
    d_model=128,                # = emb_dim (interaction width)
    vocab=0,
    source="paper §VI (Fig. 5, Table I)",
)

EXTRAS = DlrmExtras()

#: MLPerf DLRMv2 (mlcommons/inference recommendation/dlrm_v2, the MLPerf
#: Training reference's torchrec DLRM_DCN flags), batch 512, every table
#: whole (204M rows: two v5e chips' worth)
DCNV2 = DlrmExtras(
    n_dense=13, bottom_mlp=(512, 256, 128), n_tables=26,
    table_rows=(40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63,
                40000000, 3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14,
                40000000, 40000000, 40000000, 590152, 12973, 108, 36),
    emb_dim=128,
    pooling=(3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
             100, 27, 10, 3, 1, 1),
    top_mlp=(1024, 1024, 512, 256, 1), batch=512, interaction="dcnv2",
    cross_layers=3, cross_rank=512)
