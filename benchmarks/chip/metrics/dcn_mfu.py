"""Whole step (DLRMv2): the model's int8 GEMM operations per second over
the window (``work_dcnv2.int8_ops``: 2 m k n over the 14 GEMMs, 16.4 G a
request at batch 512), as a share of the chip's int8 peak, in %."""
import work_dcnv2


def read(ctx):
    recs = [r for r in ctx.window if not r["aborted"]]
    if ctx.cfg.get("interaction") != "dcnv2" or not recs \
            or ctx.window_s <= 0:
        return None
    least = work_dcnv2.int8_ops(ctx.cfg, ctx.stream.batch) \
        / float(ctx.peaks["int8_ops_per_s"])
    return 100.0 * least * len(recs) / ctx.window_s
