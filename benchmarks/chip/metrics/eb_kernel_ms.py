"""EmbeddingBag kernel: device time per request of the ops under the
``abft_eb_pallas`` jit (the Pallas kernel and its slot-coefficient
gather), in the protected lane's traced segment."""

SCOPE = "abft_eb_pallas"


def read(ctx):
    return ctx.per_request_ms(ctx.protected, SCOPE)
