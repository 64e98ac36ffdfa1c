"""qgemm kernel: device time per request of the ops under the
``abft_qgemm_pallas`` jit (every int8 GEMM of the bottom and top MLPs,
with its padding), in the protected lane's traced segment."""

SCOPE = "abft_qgemm_pallas"


def read(ctx):
    return ctx.per_request_ms(ctx.protected, SCOPE)
