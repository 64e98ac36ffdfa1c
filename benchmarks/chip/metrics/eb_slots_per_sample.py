"""EmbeddingBag kernel: slots the lookups ran (the ``eb_slots`` counter on
the engine's step events: bag positions, −1 pads included, read off the
grid of the kernel call) over the untraced window, per sample served.
DLRMv2's multi-hot sizes sum to 214; a grid that padded every bag to the
largest pool would read 2,600."""


def read(ctx):
    recs = [r for r in ctx.window if "eb_slots" in r and not r["aborted"]]
    samples = sum(r["samples"] for r in recs)
    if not samples:
        return None
    return sum(r["eb_slots"] for r in recs) / samples
