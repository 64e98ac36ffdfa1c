"""Shared benchmark utilities: CSV emit, DLRM shape set."""
from __future__ import annotations

from typing import List

# The paper's 28 Fig. 5 DLRM GEMM shapes — canonical definition lives in
# the campaign subsystem (repro.campaign.spec), re-exported here for the
# detection benchmarks.
from repro.campaign.spec import DLRM_GEMM_SHAPES as GEMM_SHAPES  # noqa: E402,F401

assert len(GEMM_SHAPES) == 28


class Csv:
    def __init__(self, header: List[str]):
        self.header = header
        self.rows: List[list] = []
        print(",".join(header), flush=True)

    def row(self, *vals):
        self.rows.append(list(vals))
        print(",".join(str(v) for v in vals), flush=True)
