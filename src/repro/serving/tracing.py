"""Engine spans on the profiler's clock.

One process-wide log of closed spans. ``span(name)`` is a context manager:
it enters ``jax.profiler.TraceAnnotation(name)``, so that under a running
profiler the span lands on the trace's host plane on the same clock as the
device ops, and on exit appends its record to the log.
``mark(name, t0, t1, rid=...)`` records a span that opened in one call and
closed in another (a request's wait in the queue). Times are
``time.monotonic()``, the clock the engine's request stamps use.

The log is a bounded deque: when it is full the oldest record goes, and the
log counts it (``dropped``) and remembers the newest end among the records
it dropped (``lost_until``), so that a reader can tell whether a window it
reads is whole. Appends take a lock, so several threads may write. The log
holds plain tuples of plain values, which the garbage collector does not
track; ``spans`` hands them out as :class:`Record`.

Span names start with ``engine.``. With no profiler running a span costs two
clock reads, an inactive annotation and one append: a few microseconds.
"""
from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional, Tuple

import jax

MAXLEN = 65_536


class Record(NamedTuple):
    """One closed span: ``name``, ``t0``/``t1`` (monotonic seconds), the
    stage index (``engine.step``) and the request id (``engine.queue``)
    where they apply."""
    name: str
    t0: float
    t1: float
    stage: Optional[int] = None
    rid: Optional[int] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Span:
    """An open span. Set ``stage`` before it closes where the stage index
    is known only at its end; ``t0``/``t1`` are set on entry and exit."""

    __slots__ = ("name", "t0", "t1", "stage", "_log", "_ann")

    def __init__(self, name: str, log: "SpanLog"):
        self.name, self._log = name, log
        self.t0 = self.t1 = 0.0
        self.stage: Optional[int] = None

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.monotonic()
        self._ann.__exit__(*exc)
        self._log._append((self.name, self.t0, self.t1, self.stage, None))


class SpanLog:
    """Closed spans, oldest first, at most ``maxlen`` of them."""

    def __init__(self, maxlen: int = MAXLEN):
        self.records: Deque[tuple] = collections.deque(maxlen=maxlen)
        self.dropped = 0
        self.lost_until = float("-inf")
        self._lock = threading.Lock()

    def _append(self, rec: tuple) -> None:
        recs = self.records
        with self._lock:
            if len(recs) == recs.maxlen:
                self.dropped += 1
                self.lost_until = max(self.lost_until, recs[0][2])
            recs.append(rec)

    def span(self, name: str) -> Span:
        return Span(name, self)

    def mark(self, name: str, t0: float, t1: float,
             rid: Optional[int] = None) -> None:
        self._append((name, t0, t1, None, rid))

    def spans(self, t0: float = float("-inf"),
              t1: float = float("inf")) -> List[Record]:
        """The records that ended in [t0, t1], oldest first."""
        return [Record(*r) for r in self.records if t0 <= r[2] <= t1]

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """(count, seconds) of the records held, by name."""
        out: Dict[str, Tuple[int, float]] = {}
        for name, a, b, _, _ in self.records:
            n, sec = out.get(name, (0, 0.0))
            out[name] = (n + 1, sec + b - a)
        return out


#: the process's log: the engine writes here and the benchmark reads here
LOG = SpanLog()


def span(name: str) -> Span:
    """A span of the process's log (see :class:`Span`)."""
    return LOG.span(name)


def mark(name: str, t0: float, t1: float, rid: Optional[int] = None) -> None:
    LOG.mark(name, t0, t1, rid=rid)


def traced(name: str):
    """Decorator: the whole call is one span of the process's log."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with LOG.span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
