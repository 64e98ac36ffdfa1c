"""Protection: device time per request of the protected lane over that of
the unprotected lane (same engine, tables and requests), minus 1, in %.

Device time is the union of the device's operations in each lane's traced
segment."""

#: the lanes this reader compares: the runner builds them in a traced run
LANES = ("unprotected",)


def read(ctx):
    ops = ctx.device_ops()
    if not ops or ctx.unprotected not in ctx.segments:
        return None
    per = {}
    for lane in (ctx.protected, ctx.unprotected):
        lo, hi, recs = ctx.segment(lane)
        if not recs:
            return None
        per[lane] = ctx.reduce.busy_ns(ops, lo, hi) / len(recs)
    if per[ctx.unprotected] <= 0:
        return None
    return 100.0 * (per[ctx.protected] / per[ctx.unprotected] - 1.0)
