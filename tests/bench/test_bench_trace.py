"""The reduction from a profiler trace to busy time, per-op device time and
the host spans around idle gaps."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from benchlib import devtrace  # noqa: E402

RECORDED = BENCH / "fixtures" / "trace_small.xplane.pb"


def test_union_merges_overlaps():
    assert devtrace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                                 (5, 8)]


def test_gap_takes_the_span_covering_most_of_it():
    spans = [(0, 10, "bench.submit"), (10, 50, "bench.step"),
             (50, 90, "bench.wait")]
    starts = [s[0] for s in spans]
    assert devtrace._span_at(spans, starts, 40, 80) == "bench.wait"
    assert devtrace._span_at(spans, starts, 12, 30) == "bench.step"
    assert devtrace._span_at(spans, starts, 95, 99) == "host.other"


def test_op_names_lose_their_instance_suffix():
    assert devtrace.op_label("%fusion.123 = bf16[2]{0} fusion(%a)") == \
        "fusion"
    assert devtrace.op_label("fusion.123.4") == "fusion"


def test_recorded_chip_trace():
    """Half a second of a deepseek-moe.chat window recorded on a v5e (the
    host's bench.* spans and the device's XLA ops, cut from the run's
    trace)."""
    red = devtrace.reduce_trace(str(RECORDED))
    assert red.chips == 1
    assert red.window_s == pytest.approx(0.5)
    assert 0 < red.busy_s < red.window_s
    # the device time of each kernel, by its class
    for label in (devtrace.PAGED_ATTENTION, devtrace.MOE_EXPERTS):
        t = red.kernel_s(label)
        assert t is not None and 0 < t < red.busy_s
        assert red.op_calls[label] > 0
    assert red.kernel_s("no_such_kernel") is None
    # loops only contain other ops; they are not counted as ops themselves
    assert not set(red.op_s) & devtrace.CONTAINERS
    # the idle time falls inside the benchmark's own host spans
    assert set(red.idle_by_span) <= {"bench.step", "bench.submit",
                                     "bench.wait", "host.other"}
    assert sum(red.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    bd = red.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) >= 1
    assert all(len(name) < 64 for name, _ in bd["device_ops"])


def test_kernel_classes_from_operands():
    attn = ('%closed_call.60 = bf16[1,16,1,128]{3,2,1,0} custom-call('
            's32[1]{0} %a, s32[1]{0} %b, s32[1,1]{1,0} %c, '
            'bf16[1,16,1,128]{3,2,1,0} %q, bf16[683,16,64,128]{3,2,1,0} %k, '
            'bf16[683,16,64,128]{3,2,1,0} %v), '
            'custom_call_target="tpu_custom_call"')
    moe = ('%closed_call.65 = bf16[48,40,2048]{2,1,0} custom-call('
           's32[48]{0} %n, s32[48]{0} %l, bf16[48,40,2048]{2,1,0} %x, '
           'bf16[48,2048,1536]{2,1,0} %g, bf16[48,2048,1536]{2,1,0} %u, '
           'bf16[48,1536,2048]{2,1,0} %o), '
           'custom_call_target="tpu_custom_call"')
    assert devtrace.op_label(attn) == devtrace.PAGED_ATTENTION
    assert devtrace.op_label(moe) == devtrace.MOE_EXPERTS
    assert devtrace.op_label("%while.116 = (s32[]) while(s32[] %t)") == \
        "while"
