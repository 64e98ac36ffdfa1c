"""Model: device time of the lane's compiled forward program per request
(its executions on the device in the protected lane's traced segment)."""

#: the serving engine's jitted DLRM step is called ``forward``
PROGRAM = "jit_forward"


def read(ctx):
    mods = ctx.device_modules()
    if not mods or ctx.protected not in ctx.segments:
        return None
    lo, hi, recs = ctx.segment(ctx.protected)
    if not recs or not any(PROGRAM in m[2] for m in mods):
        return None
    return ctx.reduce.module_time_ns(mods, PROGRAM, lo, hi) / 1e6 / len(recs)
