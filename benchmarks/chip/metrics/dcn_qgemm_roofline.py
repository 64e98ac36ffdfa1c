"""qgemm kernel (DLRMv2): least time of Algorithm 1 on every GEMM of a
request (3 bottom, 6 cross, 5 top; ``work.qgemm_work``: the larger of int8
ops at the int8 peak and bytes at the HBM peak, per GEMM), over the
kernels' device time, in %."""
import work_dcnv2

SCOPE = "abft_qgemm_pallas"


def read(ctx):
    if ctx.cfg.get("interaction") != "dcnv2":
        return None
    ms = ctx.per_request_ms(ctx.protected, SCOPE)
    if not ms:
        return None
    least = 0.0
    for m, k, n in work_dcnv2.gemm_shapes(ctx.cfg, ctx.stream.batch):
        ops, nbytes = ctx.work.qgemm_work(m, k, n)
        least += ctx.work.least_seconds(
            ops, nbytes, float(ctx.peaks["int8_ops_per_s"]), ctx.peaks)
    return 100.0 * least / (ms / 1e3)
