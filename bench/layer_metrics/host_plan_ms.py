"""Host milliseconds per traced stage spent planning it: the engine's
``engine.plan`` spans (expiry sweep, admission cap, drafting, the
scheduler's walk, the Op/B plan)."""
from benchlib.spans import ms_per_stage


def read(ctx):
    return ms_per_stage(ctx, "engine.plan")
