"""Share of their roofline the routed-expert kernels reached together (the
ragged hot-expert GEMM and the cold-expert GEMV): the least time for the
traced stages' expert work (per stage, the larger of FLOPs over peak and
bytes over bandwidth) over the kernels' summed device time."""
from benchlib.arith import live_tokens, moe_work
from benchlib.devtrace import MOE_EXPERTS


def read(ctx):
    t = ctx.trace.kernel_s(MOE_EXPERTS)
    if not t:
        return None
    pk = ctx.peaks
    least = 0.0
    for st in ctx.stages:
        f, b = moe_work(ctx.dims, live_tokens(st.rows))
        least += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
