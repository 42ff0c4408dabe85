"""Milliseconds per traced stage the host waits for the stage's results:
the engine's ``engine.sync`` spans."""
from benchlib.spans import ms_per_stage


def read(ctx):
    return ms_per_stage(ctx, "engine.sync")
