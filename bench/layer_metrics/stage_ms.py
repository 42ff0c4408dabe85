"""Host milliseconds per stage: the window over the stages that ended in
it."""


def read(ctx):
    return 1e3 * ctx.window_s / len(ctx.stages) if ctx.stages else None
