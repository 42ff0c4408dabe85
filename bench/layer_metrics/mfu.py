"""The whole step's share of the chip's bf16 peak: the FLOPs the model
needs for the window's tokens over the window times the peak."""
from benchlib.arith import model_flops


def read(ctx):
    if not ctx.stages:
        return None
    flops = sum(model_flops(ctx.dims, s.rows) for s in ctx.stages)
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
