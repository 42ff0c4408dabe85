"""Host milliseconds per traced stage spent dispatching it: the engine's
``engine.dispatch`` spans (slot claims, KV growth, input staging, block
tables, and the jitted call in ``engine.launch``)."""
from benchlib.spans import ms_per_stage


def read(ctx):
    return ms_per_stage(ctx, "engine.dispatch")
