"""The engine's own spans (``repro.serving.tracing``) over the traced
stages: the records that ended between the first traced stage's start and
the last one's end, both on ``time.monotonic``.

A reader gets nothing (None) where the program keeps no span log, where
the log holds no span of the names asked for in the window, or where it
dropped records that ended inside the window. The harness counts a
declared metric that reads nothing as unread, and the traced run as not
correct, so a metric read here belongs in BENCHMARK.json only where every
program it runs on keeps the span log (``repro.serving.tracing``).
"""
from __future__ import annotations

import statistics
from typing import Optional


def in_window(ctx, *names: str) -> Optional[list]:
    """The records named ``names`` that ended inside the traced stages."""
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    if not ctx.stages:
        return None
    t0, t1 = ctx.stages[0].t0, ctx.stages[-1].t1
    log = tracing.LOG
    if log.lost_until >= t0:
        return None
    got = [s for s in log.spans(t0, t1) if s.name in names]
    return got or None


def ms_per_stage(ctx, *names: str) -> Optional[float]:
    """Milliseconds of the spans named ``names`` per traced stage."""
    got = in_window(ctx, *names)
    if got is None:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in got) / len(ctx.stages)


def median_ms(ctx, name: str) -> Optional[float]:
    """Median length of the spans named ``name``, in milliseconds."""
    got = in_window(ctx, name)
    if got is None:
        return None
    return 1e3 * statistics.median(s.t1 - s.t0 for s in got)
