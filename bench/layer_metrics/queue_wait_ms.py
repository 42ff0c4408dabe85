"""Median milliseconds a request waited in the engine's queue, from its
entry to its KV slot: the ``engine.queue`` spans that ended inside the
traced stages."""
from benchlib.spans import median_ms


def read(ctx):
    return median_ms(ctx, "engine.queue")
