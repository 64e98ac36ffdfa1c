"""EmbeddingBag kernel (DLRMv2): least time for the bytes the lookups need
(``work_dcnv2.eb_bytes``: each slot's int8 row with its α and β, each
table's pooled output with its sum; 21.8 MB a request at batch 512) at the
HBM peak, over the kernel's device time, in %."""
import work_dcnv2

SCOPE = "abft_eb_pallas"


def read(ctx):
    if ctx.cfg.get("interaction") != "dcnv2":
        return None
    ms = ctx.per_request_ms(ctx.protected, SCOPE)
    if not ms:
        return None
    nbytes = work_dcnv2.eb_bytes(ctx.cfg, ctx.stream.batch)
    least = nbytes / float(ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
