"""Chaos invariants (PR 6): seeded fault schedules over the serving stack.

The soak asserts the strongest property the engine offers: under injected
page-allocation failures, forced evictions, latency spikes and transient
step errors, every request still finishes with greedy-token parity against
the fault-free run, ``KVManager.audit()`` is clean after every stage, and
the pool drains to fully-free. The property-based test fuzzes random
submit/step/cancel sequences across the layout × sharing × preemption
matrix through the same helper a deterministic twin drives.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import small_test_config
from repro.models.model import init_model
from repro.serving.engine import ServingEngine
from repro.serving.faults import FaultInjector, InjectedPageFault
from repro.serving.kvmanager import KVManager
from repro.serving.request import Request


@pytest.fixture(scope="module")
def chaos_setup():
    cfg = small_test_config("chaos-test")
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ---- injector --------------------------------------------------------------
def test_injector_deterministic_and_counting():
    a = FaultInjector(5, p_page_alloc_fail=0.3, p_step_error=0.3,
                      p_forced_evict=0.3, p_latency_spike=0.3)
    b = FaultInjector(5, p_page_alloc_fail=0.3, p_step_error=0.3,
                      p_forced_evict=0.3, p_latency_spike=0.3)
    seq_a = [(a.page_alloc_fails(), a.step_error(), a.forced_eviction(),
              a.latency_spike()) for _ in range(200)]
    seq_b = [(b.page_alloc_fails(), b.step_error(), b.forced_eviction(),
              b.latency_spike()) for _ in range(200)]
    assert seq_a == seq_b
    assert a.counts == b.counts
    assert a.total_faults == sum(a.counts.values()) > 0


def test_injected_page_fault_raises_in_alloc(chaos_setup):
    cfg, _ = chaos_setup
    inj = FaultInjector(0, p_page_alloc_fail=1.0, p_step_error=0.0,
                        p_forced_evict=0.0, p_latency_spike=0.0)
    kv = KVManager(cfg, 2, 32, layout="paged", page_size=8, injector=inj)
    slot = kv.allocate()
    with pytest.raises(InjectedPageFault):
        kv.ensure_len(slot, 8)
    assert inj.counts["page_alloc_fail"] == 1
    assert kv.audit(pins={}) == []   # a failed alloc must not corrupt state


# ---- the audit actually detects breakage -----------------------------------
def test_audit_detects_planted_violations(chaos_setup):
    cfg, _ = chaos_setup

    def fresh():
        kv = KVManager(cfg, 2, 32, layout="paged", page_size=8)
        slot = kv.allocate()
        kv.ensure_len(slot, 16)
        assert kv.audit(pins={}) == []
        return kv, slot

    kv, slot = fresh()               # leaked pin / phantom refcount
    pid = kv._slot_pages[slot][0]
    kv._page_refs[pid] += 1
    assert any("leaked pin" in e for e in kv.audit(pins={}))

    kv, slot = fresh()               # block table desync
    kv.block_tables[slot, 0] = 0
    assert any("desynced" in e for e in kv.audit(pins={}))

    kv, slot = fresh()               # page both free and allocated
    import heapq
    heapq.heappush(kv._page_free, kv._slot_pages[slot][1])
    assert any("both free and allocated" in e for e in kv.audit(pins={}))

    kv, slot = fresh()               # lens beyond mapped pages
    kv.lens[slot] = 99
    assert any("exceeds" in e for e in kv.audit(pins={}))

    kv, slot = fresh()               # index pointing at a free page
    kv._hash_page[1234] = kv.num_pages - 1
    assert any("free page" in e or "asymmetry" in e
               for e in kv.audit(pins={}))


# ---- the chaos soak (acceptance criterion) ---------------------------------
def _soak_requests(cfg, page_size, n=8, l_out=5):
    rng = np.random.default_rng(42)
    sys_prefix = rng.integers(0, cfg.vocab_size, 2 * page_size).tolist()
    reqs = []
    for i in range(n):
        tail = rng.integers(0, cfg.vocab_size, page_size // 2 + i).tolist()
        prompt = sys_prefix + tail if i % 4 != 3 else \
            rng.integers(0, cfg.vocab_size, 2 * page_size + 3).tolist()
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=l_out))
    return reqs


def _soak_engine(cfg, params, injector):
    # paged + prefix-share + recompute over an OVERSUBSCRIBED pool, chunked
    # prefill: every stateful mechanism of PRs 1-5 under fire at once
    return ServingEngine(cfg, params, max_slots=4, max_len=64,
                         use_duplex=False, kv_layout="paged",
                         kv_page_size=8, kv_num_pages=1 + 20,
                         prefix_share=True, preemption="recompute",
                         prefill_chunk_tokens=8, injector=injector)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_soak_parity_and_clean_drain(chaos_setup, seed):
    cfg, params = chaos_setup
    baseline = _soak_engine(cfg, params, injector=None)
    base_reqs = _soak_requests(cfg, 8)
    baseline.run(base_reqs, max_stages=2000)
    assert all(r.completed for r in base_reqs)
    expect = {r.rid: list(r.output) for r in base_reqs}

    inj = FaultInjector(seed, p_page_alloc_fail=0.05, p_forced_evict=0.08,
                        p_step_error=0.05, p_latency_spike=0.05,
                        max_retries=4)
    eng = _soak_engine(cfg, params, injector=inj)
    reqs = _soak_requests(cfg, 8)
    eng.run(reqs, max_stages=2000, stall_stages=1000)

    assert all(r.completed for r in reqs)
    # greedy parity: injected faults may reorder/replay work but can never
    # change a single sampled token
    assert {r.rid: list(r.output) for r in reqs} == expect
    st = eng.stats()
    assert st["audit_violations"] == 0, eng.audit_log[:5]
    assert eng.kv.audit(pins={}) == []
    assert eng.kv.live_pages == 0
    assert eng.kv.free_slots == eng.kv.max_slots
    assert inj.total_faults > 0, "chaos run drew no faults — raise rates"


# ---- random-ops property ---------------------------------------------------
_COMBOS = [
    ("dense", False, "none"),
    ("dense", False, "migrate"),
    ("paged", False, "none"),
    ("paged", False, "recompute"),
    ("paged", True, "none"),
    ("paged", True, "recompute"),
]


def _random_ops(cfg, params, seed):
    """Drive a random submit/step/cancel/fault schedule and audit after
    every stage; shared by the deterministic twin and the hypothesis
    fuzzer. Returns the engine for final assertions."""
    rng = np.random.default_rng(seed)
    layout, share, preemption = _COMBOS[int(rng.integers(len(_COMBOS)))]
    inj = (FaultInjector(seed, p_page_alloc_fail=0.04, p_forced_evict=0.05,
                         p_step_error=0.04, p_latency_spike=0.05)
           if rng.random() < 0.7 else None)
    eng = ServingEngine(
        cfg, params, max_slots=3, max_len=32, use_duplex=False,
        kv_layout=layout, kv_page_size=8,
        kv_num_pages=(1 + 10 if (layout == "paged"
                                 and preemption == "recompute") else None),
        prefix_share=share, preemption=preemption,
        prefill_chunk_tokens=8 if layout == "paged" else None,
        queue_cap=4, overload_policy="shed-oldest",
        injector=inj, audit_stages=True)
    prefix = rng.integers(0, cfg.vocab_size, 8).tolist()
    t = 0.0
    rid = 0
    for _ in range(int(rng.integers(15, 30))):
        op = rng.random()
        if op < 0.45:
            tail = rng.integers(0, cfg.vocab_size,
                                int(rng.integers(2, 12))).tolist()
            prompt = (prefix + tail) if rng.random() < 0.5 else tail
            req = Request(rid=rid, prompt=prompt,
                          max_new_tokens=int(rng.integers(1, 5)),
                          arrival_time=t,
                          deadline=(t + float(rng.integers(3, 30))
                                    if rng.random() < 0.3 else None))
            rid += 1
            eng.submit(req, now=t)   # queue_cap=4 sheds, never raises
        elif op < 0.6 and rid:
            eng.cancel(int(rng.integers(rid)), now=t)
        else:
            eng.step(now=t)
            t += 1.0
    for _ in range(300):
        if eng.step(now=t) is None and not eng.scheduler.has_work:
            break
        t += 1.0
    assert not eng.scheduler.has_work
    assert eng.stats()["audit_violations"] == 0, eng.audit_log[:5]
    if eng.paged:
        assert eng.kv.live_pages == 0
        assert eng.kv.audit(pins={}) == []
    assert eng.kv.free_slots == eng.kv.max_slots
    assert all(r.done for r in eng._requests.values())
    return eng


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_random_ops_deterministic_twin(chaos_setup, seed):
    cfg, params = chaos_setup
    _random_ops(cfg, params, seed)


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_random_ops_property(seed):
    cfg = small_test_config("chaos-prop")
    params = init_model(jax.random.PRNGKey(0), cfg)
    _random_ops(cfg, params, seed)


# ---- PR 9: verify spans under chaos ----------------------------------------
def test_chaos_spec_spans_parity_and_seed_reproducibility(chaos_setup):
    """A speculative verify span rides its stage's SINGLE fault draw
    (``_stage_mixed`` stages the whole span for one ``_invoke``),
    so the injector schedule stays per-stage, not per-token: injected
    faults never change a committed token relative to the fault-free
    speculative run, and a fixed chaos seed replays fault-for-fault —
    identical counts, stages and outputs — even though stages now carry
    multi-token spans and page-granular rewinds."""
    cfg, params = chaos_setup
    # repetitive prompts so the drafter actually proposes
    prompts = [[3 + i % 2, 4, 5] * 5 for i in range(4)]

    def run(injector):
        eng = ServingEngine(cfg, params, max_slots=4, max_len=64,
                            use_duplex=False, kv_layout="paged",
                            kv_page_size=8, prefix_share=True,
                            preemption="recompute", prefill_chunk_tokens=8,
                            spec_k=4, injector=injector, audit_stages=True)
        reqs = [Request(rid=i, prompt=list(p), max_new_tokens=8)
                for i, p in enumerate(prompts)]
        eng.run(reqs, max_stages=2000, stall_stages=1000)
        assert all(r.completed for r in reqs)
        assert eng.stats()["audit_violations"] == 0, eng.audit_log[:5]
        assert eng.kv.audit(pins={}) == []
        assert eng.kv.live_pages == 0
        return eng, {r.rid: list(r.output) for r in reqs}

    base, expect = run(None)
    assert base.stats()["spec_accepted"] > 0    # spans actually flew

    def inj():
        return FaultInjector(1, p_page_alloc_fail=0.04, p_forced_evict=0.05,
                             p_step_error=0.06, p_latency_spike=0.06,
                             max_retries=4)

    ia = inj()
    ea, outs_a = run(ia)
    assert outs_a == expect                     # greedy parity under fire
    assert ia.total_faults > 0, "chaos run drew no faults — raise rates"
    # same seed -> same per-stage draw schedule: the rerun must replay
    # fault-for-fault and stage-for-stage
    ib = inj()
    eb, outs_b = run(ib)
    assert outs_b == outs_a
    assert ib.counts == ia.counts
    assert eb.stats()["stages"] == ea.stats()["stages"]
