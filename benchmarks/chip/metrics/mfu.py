"""Whole step: the model's operations per second over the window, as a
share of the chip's peak, in %.

Operations are counted from the configuration's shapes
(``work.model_ops``): the int8 GEMMs against the int8 peak and the
pairwise interaction against the bf16 peak, so the share is the least
time the chip would need for the window's work over the window's time."""


def read(ctx):
    recs = [r for r in ctx.window if not r["aborted"]]
    if not recs or ctx.window_s <= 0:
        return None
    ops = ctx.work.model_ops(ctx.cfg, ctx.stream.batch)
    least = (ops["int8"] / float(ctx.peaks["int8_ops_per_s"])
             + ops["bf16"] / float(ctx.peaks["bf16_flops_per_s"]))
    return 100.0 * least * len(recs) / ctx.window_s
