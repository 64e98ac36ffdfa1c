"""Protection: ABFT checks per request, from the FaultReport counters
(``*_checks``) on the engine's step events, over the untraced window."""


def read(ctx):
    if not ctx.window:
        return None
    return sum(r["checks"] for r in ctx.window) / len(ctx.window)
