"""EmbeddingBag kernel: least time for the bytes the lookups need
(``work.eb_bytes``: each slot's int8 row with its α and β, each
pooled output with its sum) at the HBM peak, over the kernel's device
time, in %."""

SCOPE = "abft_eb_pallas"


def read(ctx):
    ms = ctx.per_request_ms(ctx.protected, SCOPE)
    if not ms:
        return None
    nbytes = ctx.work.eb_bytes(ctx.cfg, ctx.stream.batch, ctx.stream.pool)
    least = nbytes / float(ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
