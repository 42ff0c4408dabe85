"""The per-layer readers of the engine's spans: milliseconds per traced
stage and the median queue wait over the spans that ended inside the
traced stages, and no reading where the window has no such span or the log
dropped records inside it."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from benchlib.harness import Stage  # noqa: E402
from benchlib.spec import metric_reader  # noqa: E402
from repro.serving import tracing  # noqa: E402

READERS = ["host_plan_ms.throughput", "host_dispatch_ms.throughput",
           "device_wait_ms.throughput", "host_commit_ms.throughput",
           "queue_wait_ms.throughput"]


def _stage_log(t, log):
    """One 100 ms stage from ``t``: plan 2, dispatch 3 (launch 1 inside),
    sync 90, commit 1 and account 0.5 ms."""
    log.mark("engine.plan", t, t + 0.002)
    log.mark("engine.launch", t + 0.004, t + 0.005)
    log.mark("engine.dispatch", t + 0.002, t + 0.005)
    log.mark("engine.sync", t + 0.005, t + 0.095)
    log.mark("engine.commit", t + 0.095, t + 0.096)
    log.mark("engine.account", t + 0.096, t + 0.0965)
    log.mark("engine.step", t, t + 0.0965)


@pytest.fixture()
def log(monkeypatch):
    log = tracing.SpanLog()
    monkeypatch.setattr(tracing, "LOG", log)
    return log


def _ctx(first, last):
    return SimpleNamespace(stages=[Stage(10.0 + 0.1 * k, 10.1 + 0.1 * k, [])
                                   for k in range(first, last)])


def _fill(log):
    for k in range(12):                # stages 0-1 and 10-11 lie outside
        _stage_log(10.0 + 0.1 * k, log)
    # queue waits ending before, inside and after the traced stages 2-9
    for rid, (t0, t1) in enumerate([(1.0, 10.05), (9.61, 10.21),
                                    (9.5, 10.3), (9.6, 10.6),
                                    (10.5, 11.15)]):
        log.mark("engine.queue", t0, t1, rid=rid)


@pytest.mark.parametrize("name,expect", [
    ("host_plan_ms.throughput", 2.0), ("host_dispatch_ms.throughput", 3.0),
    ("device_wait_ms.throughput", 90.0), ("host_commit_ms.throughput", 1.5),
    ("queue_wait_ms.throughput", 800.0)])
def test_readers_give_the_known_values(log, name, expect):
    _fill(log)
    # the traced stages are 2-9: 10.2 to 11.0 s
    assert metric_reader(name)(_ctx(2, 10)) == pytest.approx(expect)


def test_queue_wait_is_the_median_of_the_window(log):
    for rid, wait in enumerate([5.0, 1.0, 3.0, 100.0]):
        log.mark("engine.queue", 50.0 - wait, 50.0, rid=rid)
    log.mark("engine.queue", 0.0, 200.0, rid=9)     # ends after the window
    ctx = SimpleNamespace(stages=[Stage(40.0, 45.0, []),
                                  Stage(45.0, 60.0, [])])
    assert metric_reader("queue_wait_ms.throughput")(ctx) == \
        pytest.approx(4000.0)


@pytest.mark.parametrize("name", READERS)
def test_no_span_in_the_window_reads_nothing(log, name):
    _fill(log)
    far = SimpleNamespace(stages=[Stage(500.0, 501.0, [])])
    assert metric_reader(name)(far) is None
    assert metric_reader(name)(SimpleNamespace(stages=[])) is None


@pytest.mark.parametrize("name", READERS)
def test_a_log_that_dropped_records_in_the_window_reads_nothing(
        monkeypatch, name):
    small = tracing.SpanLog(maxlen=40)
    monkeypatch.setattr(tracing, "LOG", small)
    _fill(small)
    assert small.dropped > 0
    assert metric_reader(name)(_ctx(2, 10)) is None
    # drops that all ended before the window leave it whole
    assert small.lost_until < 10.9
    assert metric_reader(name)(_ctx(9, 12)) is not None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_span_log_reads_nothing(monkeypatch, name):
    import repro.serving
    monkeypatch.delattr(repro.serving, "tracing")
    monkeypatch.setitem(sys.modules, "repro.serving.tracing", None)
    assert metric_reader(name)(_ctx(2, 10)) is None
