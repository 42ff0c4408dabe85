"""From a profiler trace (``.xplane.pb``) to device busy time, per-op device
time and the host's spans around the idle gaps.

The benchmark marks its own host spans with ``jax.profiler.TraceAnnotation``
names starting ``bench.``: ``bench.window`` around the traced window, and
``bench.submit``, ``bench.step`` and ``bench.wait`` around the calls it
makes. Device ops are the events of the ``XLA Ops`` line of each device
plane, named by their HLO text; the host and device events of one trace
share one clock.
"""
from __future__ import annotations

import bisect
import collections
import functools
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclass
class Reduction:
    window_s: float
    busy_s: float                       # union of op intervals, mean/chip
    chips: int
    op_s: Dict[str, float]              # device seconds by op name
    op_calls: Dict[str, int]
    idle_by_span: Dict[str, float]      # idle device seconds by host span
    gaps: List[Tuple[float, str]] = field(default_factory=list)

    def kernel_s(self, label: str) -> Optional[float]:
        """Device seconds of the ops labelled ``label``; None when no such
        op ran."""
        return self.op_s.get(label)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _span_at(spans: List[Tuple[float, float, str]], starts: List[float],
             a: float, b: float) -> str:
    """The host span that covers most of [a, b); ``spans`` sorted and, as
    the benchmark's calls are, one after another."""
    best, label = 0.0, "host.other"
    i = bisect.bisect_left(starts, b) - 1
    while i >= 0 and spans[i][1] > a:
        s, e, name = spans[i]
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, label = ov, name
        i -= 1
    return label


_INSTR = re.compile(r"%([\w.\-]+) = ")
_OPERAND = re.compile(r"(\w+)\[([\d,]*)\]\{[^}]*\} %")
# ops that only contain others (their bodies are events of their own)
CONTAINERS = {"while", "conditional", "call"}
PAGED_ATTENTION = "pallas:paged_attention"
MOE_EXPERTS = "pallas:moe_experts"


def kernel_class(hlo: str) -> str:
    """What a Pallas custom call computes, from its operands: the paged
    attention kernel takes scalar-prefetched lengths and block tables and
    rank-4 page pools; the expert kernels take two scalar-prefetched count
    vectors, the (E, C, d) token slots and three (E, ., .) weights."""
    args = hlo[hlo.find("custom-call(") + 12:hlo.find("custom_call_target")]
    ops = [(t, d.count(",") + 1 if d else 0) for t, d in _OPERAND.findall(args)]
    n = 0
    while n < len(ops) and ops[n][0] == "s32":
        n += 1
    rest = [rank for _, rank in ops[n:]]
    if 4 in rest:
        return PAGED_ATTENTION
    if n == 2 and rest == [3, 3, 3, 3]:
        return MOE_EXPERTS
    return "pallas:other"


@functools.lru_cache(maxsize=None)
def op_label(name: str) -> str:
    """A device op's label: the class of a Pallas kernel, else the HLO
    instruction's name without the numeric suffix that makes each
    instance unique (``%fusion.123 = ...`` -> ``fusion``)."""
    m = _INSTR.match(name)
    if not m:
        return _SUFFIX.sub("", name)
    if 'custom_call_target="tpu_custom_call"' in name:
        return kernel_class(name)
    return _SUFFIX.sub("", m.group(1))


def reduce_trace(path: str) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    device_lines = []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith("bench."):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_lines.append(line)
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    w0, w1 = window
    op_s: Dict[str, float] = collections.defaultdict(float)
    op_calls: Dict[str, int] = collections.defaultdict(int)
    idle: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    busy_total, chips = 0.0, 0
    spans.sort()
    starts = [sp[0] for sp in spans]
    for line in device_lines:
        ivs = []
        for ev in line.events:
            a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            label = op_label(ev.name)
            if label in CONTAINERS:
                continue
            op_s[label] += (b - a) * 1e-9
            op_calls[label] += 1
        if not ivs:
            continue
        chips += 1
        merged = _union(ivs)
        busy_total += sum(b - a for a, b in merged)
        prev = w0
        for a, b in merged + [(w1, w1)]:
            if a > prev:
                label = _span_at(spans, starts, prev, a)
                idle[label] += (a - prev) * 1e-9
                gaps.append(((a - prev) * 1e-9, label))
            prev = max(prev, b)
    n = max(chips, 1)
    gaps.sort(reverse=True)
    return Reduction(window_s=(w1 - w0) * 1e-9, busy_s=busy_total * 1e-9 / n,
                     chips=chips,
                     op_s={k: v / n for k, v in op_s.items()},
                     op_calls=dict(op_calls),
                     idle_by_span={k: v / n for k, v in idle.items()},
                     gaps=gaps[:10])
