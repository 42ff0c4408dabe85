"""Engine spans (``serving/tracing.py``): one ``engine.step`` per stage
carrying its index, phase spans nested inside their parents, one
``engine.queue`` span per admission, the same phases in the async loop,
spans closed by an injected fault, a bounded log that counts what it
drops, the serve line that reads the log, and the spans on the profiler's
host plane."""
import glob
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.base import small_test_config
from repro.launch.serve import span_summary
from repro.models.model import init_model
from repro.serving import tracing
from repro.serving.engine import ServingEngine
from repro.serving.faults import FaultInjector
from repro.serving.request import Request

PHASES = {"engine.plan", "engine.plan.maintain", "engine.plan.admit",
          "engine.plan.schedule", "engine.plan.duplex", "engine.dispatch",
          "engine.dispatch.inputs", "engine.launch", "engine.sync",
          "engine.commit", "engine.account", "engine.queue"}
# the spans each phase may run inside: the async loop's turn holds the
# phases the sync loop's step holds, its maintenance, and the admission
# caps that check a speculative plan
PARENTS = {"engine.plan": {"engine.step", "engine.turn"},
           "engine.plan.maintain": {"engine.plan", "engine.turn"},
           "engine.plan.admit": {"engine.plan", "engine.turn"},
           "engine.plan.draft": {"engine.plan"},
           "engine.plan.schedule": {"engine.plan"},
           "engine.plan.duplex": {"engine.plan"},
           "engine.dispatch": {"engine.step", "engine.turn"},
           "engine.dispatch.inputs": {"engine.dispatch"},
           "engine.launch": {"engine.dispatch"},
           "engine.sync": {"engine.step", "engine.turn"},
           "engine.commit": {"engine.step", "engine.turn"},
           "engine.account": {"engine.step", "engine.turn"}}


@pytest.fixture(scope="module")
def setup():
    cfg = small_test_config("tracing-test", num_layers=2, d_model=64)
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params, **kw):
    return ServingEngine(cfg, params, max_slots=3, max_len=64,
                         use_duplex=False, kv_layout="paged", kv_page_size=8,
                         prefill_chunk_tokens=6, **kw)


def _requests(vocab, n=5, l_out=4):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, vocab, int(l)).tolist(),
                    max_new_tokens=l_out)
            for i, l in enumerate(rng.integers(5, 15, n))]


def _count_allocations(eng):
    calls = []
    allocate = eng.kv.allocate

    def counted():
        calls.append(1)
        return allocate()
    eng.kv.allocate = counted
    return calls


def _enclosing(rec, recs):
    """The innermost record that may hold ``rec`` (:data:`PARENTS`) and
    whose interval does, or None."""
    around = [p for p in recs if p.name in PARENTS.get(rec.name, ())
              and p.t0 <= rec.t0 and rec.t1 <= p.t1]
    return min(around, key=lambda p: p.seconds) if around else None


def _check_nesting(recs, roots=("engine.step", "engine.queue")):
    """Children by parent; only the names ``roots`` lie inside no span."""
    children = {}
    for r in recs:
        p = _enclosing(r, recs)
        if p is None:
            assert r.name in roots, r
            continue
        children.setdefault(id(p), (p, []))[1].append(r)
    for p, kids in children.values():
        assert sum(k.seconds for k in kids) <= p.seconds + 1e-9, p.name
    return children


@pytest.fixture(scope="module")
def sync_run(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    allocs = _count_allocations(eng)
    reqs = _requests(cfg.vocab_size)
    t0 = time.monotonic()
    for r in reqs:
        eng.submit(r)
    while eng.step() is not None:
        pass
    assert all(r.completed for r in reqs)
    return eng, reqs, allocs, tracing.LOG.spans(t0)


def test_one_step_span_per_report(sync_run):
    eng, _, _, recs = sync_run
    steps = [s for s in recs if s.name == "engine.step"]
    staged = [s for s in steps if s.stage is not None]
    assert [s.stage for s in staged] == [r.stage_index for r in eng.reports]
    # the last call formed no stage: its span carries no stage index
    assert steps[-1].stage is None
    assert len(steps) == len(eng.reports) + 1


def test_phase_spans_nest_inside_their_parents(sync_run):
    _, _, _, recs = sync_run
    assert PHASES <= {s.name for s in recs}
    children = _check_nesting(recs)
    parents = {(k.name, p.name) for p, kids in children.values()
               for k in kids}
    assert {("engine.plan", "engine.step"),
            ("engine.plan.maintain", "engine.plan"),
            ("engine.plan.admit", "engine.plan"),
            ("engine.plan.schedule", "engine.plan"),
            ("engine.plan.duplex", "engine.plan"),
            ("engine.dispatch", "engine.step"),
            ("engine.dispatch.inputs", "engine.dispatch"),
            ("engine.launch", "engine.dispatch"),
            ("engine.sync", "engine.step"),
            ("engine.commit", "engine.step"),
            ("engine.account", "engine.step")} <= parents
    # every stage holds exactly one of each top-level phase
    for p, kids in children.values():
        if p.name == "engine.step" and p.stage is not None:
            names = sorted(k.name for k in kids)
            assert names == ["engine.account", "engine.commit",
                             "engine.dispatch", "engine.plan",
                             "engine.sync"]


def test_host_gap_is_read_off_the_sync_and_launch_spans(sync_run):
    eng, _, _, recs = sync_run
    syncs = [s.t1 for s in recs if s.name == "engine.sync"]
    launches = [s.t1 for s in recs if s.name == "engine.launch"]
    assert len(syncs) == len(launches) == len(eng.reports)
    gaps = [b - a for a, b in zip(syncs, launches[1:])]
    st = eng.stats()
    assert st["gap_stages"] == len(gaps)
    assert st["host_gap_s"] == pytest.approx(sum(gaps), abs=1e-12)


def test_queue_span_per_admission(sync_run):
    _, reqs, allocs, recs = sync_run
    queue = [s for s in recs if s.name == "engine.queue"]
    assert len(queue) == len(allocs) == len(reqs)
    assert sorted(s.rid for s in queue) == [r.rid for r in reqs]
    assert all(0 <= s.seconds for s in queue)
    # with 3 slots for 5 requests, the last two wait for a slot to free
    waited = sorted(queue, key=lambda s: s.seconds)
    assert waited[-1].seconds > waited[0].seconds


def test_async_loop_emits_the_same_phases(setup, sync_run):
    cfg, params = setup
    eng = _engine(cfg, params)
    reqs = _requests(cfg.vocab_size)
    t0 = time.monotonic()
    eng.run_async(reqs)
    recs = tracing.LOG.spans(t0)
    names = {s.name for s in recs}
    assert {s.name for s in sync_run[3]} - {"engine.step"} <= names
    assert "engine.turn" in names and "engine.step" not in names
    # the loop plans and dispatches its first stage outside any turn
    _check_nesting(recs, roots=("engine.turn", "engine.queue",
                                "engine.plan", "engine.dispatch"))
    assert sorted(s.rid for s in recs if s.name == "engine.queue") == \
        [r.rid for r in reqs]


def test_drafting_is_a_child_of_the_plan(setup):
    cfg, params = setup
    eng = _engine(cfg, params, spec_k=2)
    reqs = _requests(cfg.vocab_size, n=2, l_out=6)
    t0 = time.monotonic()
    eng.run(reqs)
    recs = tracing.LOG.spans(t0)
    children = _check_nesting(recs)
    drafts = [k for p, kids in children.values() for k in kids
              if k.name == "engine.plan.draft"]
    assert drafts and all(p.name == "engine.plan"
                          for p, kids in children.values() for k in kids
                          if k.name == "engine.plan.draft")
    assert len(drafts) == sum(1 for s in recs if s.name == "engine.plan")


def test_injected_faults_close_their_spans(setup):
    cfg, params = setup
    inj = FaultInjector(5, p_page_alloc_fail=0.0, p_forced_evict=0.3,
                        p_step_error=0.4, p_latency_spike=0.0, max_retries=1)
    eng = _engine(cfg, params, injector=inj, preemption="recompute")
    allocs = _count_allocations(eng)
    reqs = _requests(cfg.vocab_size)
    t0 = time.monotonic()
    eng.run(reqs, stall_stages=1000)
    recs = tracing.LOG.spans(t0)
    assert all(r.completed for r in reqs)
    assert inj.counts["step_error"] > 0 and eng.stage_aborts > 0
    children = _check_nesting(recs)
    steps = {s.stage: s for s in recs if s.name == "engine.step"}
    assert set(steps) - {None} == {r.stage_index for r in eng.reports}
    # an aborted stage's step closed with its dispatch span, and no sync
    kids = {p.stage: {k.name for k in ks} for p, ks in children.values()
            if p.name == "engine.step"}
    aborted = [r.stage_index for r in eng.reports if r.aborted]
    assert aborted
    for i in aborted:
        assert "engine.dispatch" in kids[i] and "engine.sync" not in kids[i]
    # an aborted admission or a preemption returns the request to the
    # queue, and its next slot claim records a second span
    queue = [s for s in recs if s.name == "engine.queue"]
    assert len(queue) == len(allocs) > len(reqs)
    assert {s.rid for s in queue} == {r.rid for r in reqs}


def test_log_is_bounded_and_counts_what_it_dropped():
    log = tracing.SpanLog(maxlen=4)
    for i in range(10):
        log.mark("engine.queue", float(i), i + 0.5, rid=i)
    assert len(log.records) == 4 and log.dropped == 6
    assert log.lost_until == 5.5
    assert [s.rid for s in log.spans()] == [6, 7, 8, 9]
    assert [s.rid for s in log.spans(7.0, 8.5)] == [7, 8]
    with log.span("engine.step") as outer:
        with log.span("engine.sync"):
            pass
        outer.stage = 3
    inner, step = log.spans()[-2:]
    assert (inner.name, step.name, step.stage) == ("engine.sync",
                                                   "engine.step", 3)
    assert step.t0 <= inner.t0 <= inner.t1 <= step.t1
    assert log.dropped == 8
    assert log.totals()["engine.queue"][0] == 2
    with pytest.raises(ValueError):
        with log.span("engine.step"):
            raise ValueError
    assert log.spans()[-1].name == "engine.step" and log.dropped == 9
    # records are plain tuples, which the garbage collector leaves alone
    import gc
    gc.collect()
    assert not any(gc.is_tracked(r) for r in log.records)


def test_threads_lose_no_record():
    log = tracing.SpanLog(maxlen=64)
    n_threads, n_spans = 16, 2000
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(n_spans):
                with log.span("engine.step"):
                    log.mark("engine.queue", float(i), float(i), rid=k)
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert len(log.records) + log.dropped == 2 * n_threads * n_spans


def test_profiler_host_plane_holds_the_spans(setup, sync_run, tmp_path):
    from jax.profiler import ProfileData
    cfg, _ = setup
    eng = sync_run[0]
    eng.submit(Request(rid=100, prompt=list(range(1, 9)), max_new_tokens=2))
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.step() is not None:
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host") for line in plane.lines
              for ev in line.events]
    steps = [ev for ev in events if ev.name == "engine.step"]
    syncs = [ev for ev in events if ev.name == "engine.sync"]
    assert steps and syncs
    for ev in syncs:
        assert any(st.start_ns <= ev.start_ns and ev.end_ns <= st.end_ns
                   for st in steps)


def test_serve_line_reads_the_log():
    log = tracing.SpanLog(maxlen=9)
    log.mark("engine.queue", 0.0, 9.0, rid=1)      # dropped below
    for i, t in enumerate((10.0, 11.0)):
        log.mark("engine.queue", t - 2.0, t, rid=7)
        log.mark("engine.sync", t, t + 0.5)
        log._append(("engine.step", t, t + 0.75, i, None))
    log.mark("engine.step", 12.0, 12.25)           # idle: no stage formed
    log.mark("engine.turn", 13.0, 14.0)
    log.mark("engine.sync", 13.0, 13.5)
    line = span_summary(log)
    assert log.dropped == 1
    assert line == ("[serve] engine spans over 3 stages, mean ms/stage: "
                    "engine.step=583.333 engine.sync=500.000 "
                    "engine.turn=333.333; engine.queue mean 2000.000 ms "
                    "over 2 admissions of 1 requests; 1 older records "
                    "dropped")
