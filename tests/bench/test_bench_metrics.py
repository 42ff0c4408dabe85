"""The end-to-end metric arithmetic: tails over every request, misses for
failed and unfinished ones, and tokens counted inside the window only."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from benchlib import harness  # noqa: E402
from benchlib.harness import Sent, Stage  # noqa: E402


def _req(rid, times, reason="length", first=None):
    return SimpleNamespace(
        rid=rid, token_times=list(times), finish_reason=reason,
        first_token_time=first if first is not None else
        (times[0] if times else None))


def test_ttft_counts_misses_as_the_wait():
    sent = [Sent(_req(0, [1.0, 1.1, 1.2]), due=0.5, sent=0.5, phase="w"),
            Sent(_req(1, [], reason=None), due=2.0, sent=2.0, phase="w"),
            Sent(_req(2, [3.0], reason="expired"), due=1.0, sent=1.0,
                 phase="w")]
    ttft, tbt = harness.latency_samples(sent, t_end=10.0)
    # served 0.5 s after it was due; never served: waited until the end;
    # failed after a token: a miss, counted the same way
    assert ttft == pytest.approx([0.5, 8.0, 9.0])
    assert tbt == pytest.approx([0.1, 0.1])


def test_tail_is_over_all_requests():
    sent = [Sent(_req(i, [float(i)]), due=0.0, sent=0.0, phase="w")
            for i in range(1, 101)]
    ttft, _ = harness.latency_samples(sent, t_end=1000.0)
    assert harness.percentile(ttft, 90) == pytest.approx(
        np.percentile(np.arange(1, 101), 90))
    # one request never served moves the tail up
    sent[0] = Sent(_req(0, [], reason=None), due=0.0, sent=0.0, phase="w")
    ttft, _ = harness.latency_samples(sent, t_end=1000.0)
    assert max(ttft) == 1000.0
    assert harness.percentile(ttft, 90) > np.percentile(np.arange(1, 101),
                                                        90)


def test_window_tokens_count_only_the_window():
    stages = [Stage(0.0, 0.9, [("chunk", 0, 256, False, 0)]),
              Stage(0.9, 1.5, [("chunk", 256, 300, True, 0),
                               ("decode", 40)]),
              Stage(1.5, 2.5, [("chunk", 0, 100, True, 2)]),
              Stage(2.5, 3.5, [("chunk", 0, 64, True, 1)])]
    sent = [Sent(_req(0, [1.5, 2.0, 3.2]), 0.0, 0.0, "w"),
            Sent(_req(1, [3.5]), 0.0, 0.0, "w"),
            Sent(_req(2, [2.5, 2.6], reason="cancelled"), 0.0, 0.0, "w")]
    prompt, out = harness.window_tokens(stages, sent, t0=1.0, t1=3.0)
    # the stage ending at 0.9 is before the window, the one at 3.5 after;
    # request 2 failed, so its chunk and tokens do not count
    assert prompt == 44
    assert out == 2


def test_sample_holds_the_longest_and_is_seeded():
    done = [Sent(SimpleNamespace(output=[0] * n, l_in=10), 0, 0, "w")
            for n in (5, 250, 7, 9, 11, 13, 15, 17, 19, 21, 23)]
    a = harness.pick_sample(done, 7)
    assert a[0] is done[1]
    assert len(a) == harness.CHECK_REQUESTS
    assert [id(s) for s in harness.pick_sample(done, 7)] == \
        [id(s) for s in a]
    assert [id(s) for s in harness.pick_sample(done, 8)] != \
        [id(s) for s in a]


def test_sample_grows_until_it_holds_enough_tokens():
    done = [Sent(SimpleNamespace(output=[0] * 13, l_in=10), 0, 0, "w")
            for _ in range(40)]
    a = harness.pick_sample(done, 3)
    assert sum(len(s.req.output) for s in a) >= harness.CHECK_TOKENS
    assert len(a) == -(-harness.CHECK_TOKENS // 13)


LIMITS = {"logit_gap_limit": 0.1, "logit_gap_p99_limit": 0.05}


def test_judge_holds_each_number_to_its_limit():
    n = harness.MIN_CHECKED
    gaps = np.full(n, 0.01)
    checks, ok = harness.judge(gaps, 0, LIMITS)
    assert ok and list(checks) == ["logit_gap", "logit_gap_p99",
                                   "tokens_checked", "tokens_outside_vocab"]
    assert not harness.judge(np.append(gaps, 0.2), 0, LIMITS)[1]
    assert not harness.judge(gaps[:-1], 0, LIMITS)[1]
    assert not harness.judge(gaps, 1, LIMITS)[1]
    assert not harness.judge(gaps, 0, {})[1]


def test_judge_fails_a_traced_run_that_lost_a_kernel_or_a_metric():
    gaps = np.full(harness.MIN_CHECKED, 0.01)
    assert harness.judge(gaps, 0, LIMITS, {"kernels_missing": 0,
                                           "per_layer_unread": 0})[1]
    assert not harness.judge(gaps, 0, LIMITS, {"kernels_missing": 1})[1]
    checks, ok = harness.judge(gaps, 0, LIMITS, {"per_layer_unread": 2})
    assert not ok and checks["per_layer_unread"] == {"value": 2, "limit": 0}
