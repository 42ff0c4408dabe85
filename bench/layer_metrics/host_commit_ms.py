"""Host milliseconds per traced stage spent committing it: the engine's
``engine.commit`` (tokens, lengths, retirement) and ``engine.account``
(router counts, traffic model, report) spans."""
from benchlib.spans import ms_per_stage


def read(ctx):
    return ms_per_stage(ctx, "engine.commit", "engine.account")
