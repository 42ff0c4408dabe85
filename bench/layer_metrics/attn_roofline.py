"""Share of its roofline the paged attention kernel reached: the least time
the chip needs for the traced stages' attention work (per stage, the decode call
and the chunk call each bound by the larger of FLOPs over peak and bytes
over bandwidth) over the kernel's summed device time."""
from benchlib.arith import attn_work
from benchlib.devtrace import PAGED_ATTENTION


def read(ctx):
    t = ctx.trace.kernel_s(PAGED_ATTENTION)
    if not t:
        return None
    pk = ctx.peaks
    least = 0.0
    for st in ctx.stages:
        for kind in ("decode", "chunk"):
            f, b = attn_work(ctx.dims, [r for r in st.rows if r[0] == kind])
            least += max(f / pk["bf16_flops_per_s"],
                         b / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
