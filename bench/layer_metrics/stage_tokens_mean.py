"""Live tokens per stage, as the scheduler packed them: prompt tokens of
the chunks and one token per decode row, over the stages that ended in the
traced window."""
from benchlib.arith import live_tokens


def read(ctx):
    if not ctx.stages:
        return None
    return sum(live_tokens(st.rows) for st in ctx.stages) / len(ctx.stages)
