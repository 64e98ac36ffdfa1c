"""Serving engine: host time per request outside the engine's blocked step.

Each request's latency on the host clock minus the duration of its step
as the engine timed it (``StepEvent.duration_s``, from ``_timed``), as a
mean over the requests of the untraced window."""


def read(ctx):
    recs = [r for r in ctx.window if not r["aborted"]]
    if not recs:
        return None
    return 1e3 * sum(r["latency_s"] - r["step_s"] for r in recs) / len(recs)
