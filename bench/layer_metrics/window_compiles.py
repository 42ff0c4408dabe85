"""Programs compiled, or loaded from the persistent cache, inside the
window, from JAX's monitoring events: each is a stage shape the warm-up
did not reach."""


def read(ctx):
    return ctx.window_programs
