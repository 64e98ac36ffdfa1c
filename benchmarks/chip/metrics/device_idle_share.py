"""Device: share of the protected lane's traced segment in which no
operation ran on the device, in %."""


def read(ctx):
    ops = ctx.device_ops()
    if not ops or ctx.protected not in ctx.segments:
        return None
    lo, hi, _ = ctx.segment(ctx.protected)
    return 100.0 * (1.0 - ctx.reduce.busy_ns(ops, lo, hi) / (hi - lo))
