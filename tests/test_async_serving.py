"""Async serving loop (PR 8): pipelined plan/dispatch/commit.

The contract under test: ``run_async`` overlaps host scheduling with
device compute — speculative next-stage planning, chained dispatch on
in-flight tokens, deferred commit accounting — WITHOUT changing a single
greedy token relative to ``run``, across every KV layout the engine
supports, while staying safe against threads submitting and cancelling
work mid-run.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.base import small_test_config
from repro.models.model import init_model
from repro.serving.engine import ServingEngine
from repro.serving.request import Request
from repro.serving.scheduler import ContinuousBatchingScheduler


@pytest.fixture(scope="module")
def async_setup():
    cfg = small_test_config("async-test", num_layers=2, d_model=64)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# every flavor the parity acceptance names: dense, paged, prefix-share,
# chunked
FLAVORS = {
    "dense": dict(kv_layout="dense"),
    "paged": dict(kv_layout="paged", kv_page_size=8),
    "paged_chunked": dict(kv_layout="paged", kv_page_size=8,
                          prefill_chunk_tokens=6),
    "prefix_share": dict(kv_layout="paged", kv_page_size=8,
                         prefill_chunk_tokens=8, prefix_share=True),
}


def _mk_reqs(vocab, n=6, l_out=5, shared_prefix=False):
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, vocab, 16).tolist() if shared_prefix else []
    reqs = []
    for i in range(n):
        l_in = int(rng.integers(4, 20))
        prompt = prefix + rng.integers(0, vocab, l_in).tolist()
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=l_out))
    return reqs


def _run(cfg, params, kw, *, use_async, **ekw):
    eng = ServingEngine(cfg, params, max_slots=4, max_len=64,
                        use_duplex=False, **kw, **ekw)
    reqs = _mk_reqs(cfg.vocab_size,
                    shared_prefix=kw.get("prefix_share", False))
    if use_async:
        eng.run_async(reqs)
    else:
        eng.run(reqs)
    return eng, {r.rid: list(r.output) for r in reqs}


# ---------------------------------------------------------------------------
# parity: async greedy tokens byte-identical to sync, every flavor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flavor", sorted(FLAVORS))
def test_async_sync_greedy_parity(async_setup, flavor):
    cfg, params = async_setup
    kw = FLAVORS[flavor]
    e_sync, sync_out = _run(cfg, params, kw, use_async=False)
    e_async, async_out = _run(cfg, params, kw, use_async=True)
    assert sync_out == async_out, f"{flavor}: async diverged from sync"
    assert all(len(t) == 5 for t in async_out.values())
    # pool drains fully-free in both loops
    assert e_async.kv.free_slots == e_async.kv.max_slots
    if kw.get("kv_layout") == "paged":
        assert e_async.kv.live_pages == 0
        assert e_async.kv.audit(pins={}) == []
    # the pipeline actually pipelined: speculative plans were dispatched
    st = e_async.stats()
    assert st["spec_hits"] > 0


def test_async_chained_dispatch_zero_gap(async_setup):
    """Chained stages enqueue N+1 before N materializes: the recorded
    host gap for them is structurally zero, and a decode-heavy workload
    chains nearly every stage."""
    cfg, params = async_setup
    eng, _ = _run(cfg, params, dict(kv_layout="paged", kv_page_size=8,
                                    prefill_chunk_tokens=8),
                  use_async=True)
    st = eng.stats()
    assert st["chained_stages"] > 0
    assert st["chained_stages"] <= st["spec_hits"]
    # gap accounting only accumulates over non-chained stages, so the
    # mean per-stage gap must be far below a sync host turnaround
    assert eng.gap_stages >= st["chained_stages"]


# ---------------------------------------------------------------------------
# thread safety: submit/cancel/stats while the loop runs
# ---------------------------------------------------------------------------

def test_threaded_submit_cancel_soak(async_setup):
    """Feed the running async loop from another thread — late submits are
    picked up, cancels release resources — then verify every request hit
    a terminal state exactly once, audits stayed clean, and the pool
    drained fully-free."""
    cfg, params = async_setup
    eng = ServingEngine(cfg, params, max_slots=4, max_len=64,
                        use_duplex=False, kv_layout="paged", kv_page_size=8,
                        prefill_chunk_tokens=8, prefix_share=True,
                        audit_stages=True)
    rng = np.random.default_rng(11)
    initial = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, 12).tolist(), max_new_tokens=8)
        for i in range(4)]
    late, cancelled = [], []
    stats_polls = []

    def feeder():
        for i in range(4, 16):
            r = Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, 10).tolist(),
                        max_new_tokens=6)
            late.append(r)
            eng.submit(r)
            if i % 3 == 0:
                victim = i - 2
                if eng.cancel(victim):
                    cancelled.append(victim)
            stats_polls.append(eng.stats(reset=(i % 2 == 0)))
            time.sleep(0.002)

    t = threading.Thread(target=feeder)
    t.start()
    eng.run_async(initial, max_stages=5000)
    t.join()
    # drain whatever landed after the loop saw an empty scheduler
    eng.run_async([], max_stages=5000)

    everyone = initial + late
    assert all(r.done for r in everyone)
    by_reason = {}
    for r in everyone:
        by_reason.setdefault(r.finish_reason, []).append(r.rid)
    assert sorted(by_reason.get("cancelled", [])) == sorted(cancelled)
    assert all(len(r.output) == r.max_new_tokens for r in everyone
               if r.finish_reason == "length")
    # pool drains fully-free, per-stage audits stayed clean
    assert eng.kv.free_slots == eng.kv.max_slots
    assert eng.kv.live_pages == 0
    assert eng.stats()["audit_violations"] == 0
    # concurrent stats() polls were well-formed windows
    assert all("spec_hits" in s and "stages" in s and "delta" in s
               for s in stats_polls)


def test_cancel_between_async_stages(async_setup):
    """A cancel landing while a stage is in flight discards that row at
    commit instead of committing a token for a dead request."""
    cfg, params = async_setup
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32,
                        use_duplex=False)
    reqs = [Request(rid=i, prompt=[1, 2, 3, 4], max_new_tokens=20)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    # prime the pipeline a few ticks, then cancel rid 0 mid-flight
    for _ in range(3):
        eng.step_async()
    n0 = len(reqs[0].output)
    assert eng.cancel(0)
    while eng.scheduler.has_work:
        eng.step_async()
    eng.step_async()                    # commit the trailing in-flight stage
    assert reqs[0].finish_reason == "cancelled"
    assert len(reqs[0].output) == n0    # nothing committed after the cancel
    assert reqs[1].done and len(reqs[1].output) == 20
    assert eng.kv.free_slots == eng.kv.max_slots


# ---------------------------------------------------------------------------
# priority aging (satellite): queued work cannot starve
# ---------------------------------------------------------------------------

def test_priority_aging_prevents_starvation():
    """A low-priority request behind a stream of high-priority arrivals is
    promoted after aging_rounds passed-over stages; without aging it
    stays parked behind every newcomer."""
    def drive(aging_rounds):
        s = ContinuousBatchingScheduler(max_prefill_seqs=1,
                                        aging_rounds=aging_rounds)
        low = Request(rid=0, prompt=[1, 2], max_new_tokens=1, priority=0)
        s.submit(low)
        admitted_at = None
        for i in range(1, 12):
            s.submit(Request(rid=i, prompt=[1, 2], max_new_tokens=1,
                             priority=2))
            d = s.next_stage(free_slots=1)
            assert d is not None and len(d.admitted) == 1
            r = d.admitted[0]
            if r.rid == 0:
                admitted_at = i
                break
            # retire the admitted request so the slot frees again
            r.record_token(1, 0.0)
            s.commit_stage(d)
            s.remove(r)
        return admitted_at, s.aging_promotions

    starved_at, _ = drive(aging_rounds=None)
    assert starved_at is None           # strict bands: rid 0 never runs
    aged_at, promotions = drive(aging_rounds=3)
    assert aged_at is not None          # aging got it admitted
    assert promotions >= 2              # reached band 2 via 2 x 3 skips


# ---------------------------------------------------------------------------
# fleet + CLI integration
# ---------------------------------------------------------------------------

def test_fleet_async_steps(async_setup):
    from repro.serving.fleet import Fleet
    cfg, params = async_setup

    def make(i, injector):
        del i
        return ServingEngine(cfg, params, max_slots=4, max_len=64,
                             use_duplex=False, injector=injector)

    outs = {}
    for async_steps in (False, True):
        fleet = Fleet(make, 2, router="round-robin",
                      async_steps=async_steps)
        reqs = _mk_reqs(cfg.vocab_size, n=6, l_out=4)
        fleet.run(reqs)
        assert all(r.done for r in reqs)
        outs[async_steps] = {r.rid: list(r.output) for r in reqs}
    assert outs[False] == outs[True]    # replica-level parity


def test_serve_cli_async_profile(tmp_path, monkeypatch):
    """`serve --async --profile DIR` exits 0 and writes a trace; the
    printed stats include the async pipeline counters."""
    from repro.launch.serve import main
    # keep main()'s compile cache out of the checkout for this test process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    prof = tmp_path / "trace"
    rc = main(["--arch", "tiny-dense", "--no-duplex", "--async",
               "--requests", "3", "--l-in", "8", "--l-out", "3",
               "--max-slots", "2", "--max-len", "32",
               "--profile", str(prof)])
    assert rc == 0
    assert any(prof.rglob("*")), "profiler wrote no trace files"
