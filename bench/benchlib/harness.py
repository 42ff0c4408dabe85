"""One run of one cell: set-up, the measured window, the drain, the check
against the reference, and the result line's contents.

The system under test is ``ServingEngine`` driven through ``submit()`` and
``step()`` (the synchronous loop ``serve`` runs), built as
``serve --kernels --kv-layout paged --prefill-chunk 256`` builds it, with
the deployment's sizes from the configuration file and every other
argument at the program's default.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import math
import sys
import time
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from benchlib import reference, weights
from benchlib.compiles import (CompileCounter, StageCalls, missing_kernels,
                               served_kernels)
from benchlib.loadgen import Arrival, Traffic, rng_for
from benchlib.model import Dims, dims_of
from benchlib.spec import Cell, metric_reader, peaks

# kernel names the per-layer readers and the served-program check look for
ATTN_KERNEL = "_paged_kernel"
HOT_KERNEL = "_ragged_moe_gemm_kernel"
COLD_KERNEL = "_ragged_moe_gemv_kernel"

WINDOW_STREAM = 0          # traffic stream of the window's requests
WARM_STREAM = 1000         # warm-up pass p draws stream WARM_STREAM + p
SAMPLE_STREAM = 9999       # which finished requests the check compares
CHECK_REQUESTS = 8         # requests the reference recomputes, at least
CHECK_TOKENS = 300         # and served tokens it compares, at least
MIN_CHECKED = 200          # served tokens a check must compare at least
DRAIN_MAX_S = 90.0         # the longest the run waits for window requests
FAILED = ("cancelled", "shed", "rejected", "expired", "lost")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ program
def program_config(cfg_file: dict, dims: Dims):
    """The program's configuration: the registry's architecture (layer
    pattern, QK norm, gated FFN) with every size from the configuration
    file, so that the file is the configuration as it is run."""
    from repro.configs.base import MOE, Segment
    from repro.configs.registry import get_config
    full = get_config(cfg_file["arch"])
    segs = []
    for seg in full.segments:
        (kind,) = seg.pattern
        n = (dims.layers - dims.first_dense if kind.ffn == MOE
             else dims.first_dense)
        if n:
            segs.append(Segment(seg.pattern, n))
    moe = dataclasses.replace(
        full.moe, num_experts=dims.experts, top_k=dims.top_k,
        d_ff_expert=dims.expert_ff, norm_topk_probs=dims.norm_topk,
        num_shared_experts=(cfg_file.get("n_shared_experts", 0)
                            if dims.shared_ff else 0),
        d_ff_shared=dims.shared_ff)
    return dataclasses.replace(
        full, num_layers=dims.layers, segments=tuple(segs),
        d_model=dims.hidden, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        d_ff=dims.dense_ff or dims.expert_ff, vocab_size=dims.vocab,
        norm_eps=dims.eps, rope_theta=dims.rope_theta, moe=moe).validate()


def make_engine(prog_cfg, params, dep: dict):
    from repro.serving.engine import ServingEngine
    from repro.serving.kvmanager import pages_for_budget
    pages = pages_for_budget(prog_cfg, dep["page_size"], dep["kv_pool_bytes"])
    return ServingEngine(prog_cfg, params, max_slots=dep["max_slots"],
                         max_len=dep["max_len"], use_kernels=True,
                         use_duplex=True, moe_ragged=True,
                         kv_layout="paged", kv_page_size=dep["page_size"],
                         kv_num_pages=1 + pages,
                         prefill_chunk_tokens=dep["prefill_chunk"])


# ------------------------------------------------------------------- driver
@dataclass
class Stage:
    t0: float
    t1: float
    rows: List[tuple]          # arith rows, chunk rows carry (..., last, rid)


@dataclass
class Sent:
    req: object
    due: float                 # absolute due time (host monotonic clock)
    sent: float
    phase: str
    ended: Optional[float] = None   # end of the stage that finished it


class Driver:
    """Submits and steps; reads each stage's rows off the requests (a
    decode row: its context; a chunk: the prompt span it covered)."""

    def __init__(self, eng):
        import jax
        self.eng = eng
        self.clock = time.monotonic
        self.annotate = jax.profiler.TraceAnnotation
        self.next_rid = 0
        self.live: Dict[int, object] = {}
        self.sent: List[Sent] = []
        self.by_rid: Dict[int, Sent] = {}
        self.stages: List[Stage] = []

    def submit(self, arr: Arrival, due: float, phase: str):
        from repro.serving.request import Request
        req = Request(rid=self.next_rid, prompt=arr.prompt.tolist(),
                      max_new_tokens=arr.max_new, arrival_time=due)
        self.next_rid += 1
        with self.annotate("bench.submit"):
            self.eng.submit(req)
        self.live[req.rid] = req
        self.sent.append(Sent(req, due, self.clock(), phase))
        self.by_rid[req.rid] = self.sent[-1]
        return req

    def step(self) -> bool:
        before = {rid: (r.prefill_pos, len(r.output))
                  for rid, r in self.live.items()}
        t0 = self.clock()
        with self.annotate("bench.step"):
            rep = self.eng.step()
        t1 = self.clock()
        if rep is None:
            return False
        rows = []
        for rid, (pp, n_out) in before.items():
            r = self.live[rid]
            if r.prefill_pos > pp:
                rows.append(("chunk", pp, r.prefill_pos,
                             len(r.output) > n_out, rid))
            elif len(r.output) > n_out:
                rows.append(("decode", r.l_in + n_out))
            if r.done:
                del self.live[rid]
                self.by_rid[rid].ended = t1
        self.stages.append(Stage(t0, t1, rows))
        return True

    def wait(self, until: float):
        with self.annotate("bench.wait"):
            dt = until - self.clock()
            if dt > 0:
                time.sleep(min(dt, 0.05))

    def cancel_all(self):
        for rid in list(self.live):
            self.eng.cancel(rid)
            self.live.pop(rid)


class OpenSource:
    """Arrivals of consecutive blocks of one open-loop traffic, block k due
    from ``base + k * seconds``."""

    def __init__(self, traffic: Traffic, stream0: int, base: float):
        self.traffic, self.stream0, self.base = traffic, stream0, base
        self._it = self._arrivals()
        self.pending = next(self._it)

    def _arrivals(self) -> Iterator[Tuple[float, Arrival, int]]:
        k = 0
        while True:
            t = self.base + k * self.traffic.seconds
            for a in self.traffic.block(self.stream0 + k):
                yield t + a.due, a, k
            k += 1

    def feed(self, drv: Driver, now: float, phase_of) -> Optional[float]:
        """Submit what is due; return when the next one is due."""
        while self.pending[0] <= now:
            due, arr, k = self.pending
            drv.submit(arr, due, phase_of(k))
            self.pending = next(self._it)
        return self.pending[0]

    def feed_done(self, w1: float) -> bool:
        """Every request due before ``w1`` has been sent."""
        return self.pending[0] >= w1


class ClosedSource:
    """``clients`` clients, each sending its next request of the pool as
    soon as its last one finished."""

    def __init__(self, traffic: Traffic, stream0: int):
        self.traffic = traffic
        self.clients: List[Optional[object]] = [None] * traffic.clients
        self._it = self._requests(stream0)

    def _requests(self, stream0: int) -> Iterator[Arrival]:
        k = 0
        while True:
            yield from self.traffic.block(stream0 + k)
            k += 1

    def feed(self, drv: Driver, now: float, phase_of) -> Optional[float]:
        for i, r in enumerate(self.clients):
            if r is None or r.done:
                self.clients[i] = drv.submit(next(self._it), drv.clock(),
                                             phase_of(0))
        return None

    def feed_done(self, w1: float) -> bool:
        return True


def drive(drv: Driver, src, stop, phase_of):
    """Feed and step until ``stop(now)``."""
    while True:
        now = drv.clock()
        if stop(now):
            return
        nxt = src.feed(drv, now, phase_of)
        if not drv.step():
            drv.wait(nxt if nxt is not None else now + 0.01)


def programs_text(c0, c1) -> str:
    n, hits = c1[0] - c0[0], c1[2] - c0[2]
    return (f"{n - hits} programs compiled and {hits} loaded from the cache "
            f"({c1[1] - c0[1]:.1f} s)")


# ------------------------------------------------------------------ metrics
def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def failed(req) -> bool:
    return req.finish_reason in FAILED


def window_tokens(stages: List[Stage], sent: List[Sent], t0: float,
                  t1: float) -> Tuple[int, int]:
    """(prompt tokens prefilled, output tokens made) in [t0, t1], leaving
    out the tokens of requests that failed."""
    bad = {s.req.rid for s in sent if failed(s.req)}
    prompt = sum(r[2] - r[1] for st in stages if t0 <= st.t1 <= t1
                 for r in st.rows if r[0] == "chunk" and r[4] not in bad)
    out = sum(1 for s in sent if not failed(s.req)
              for t in s.req.token_times if t0 <= t <= t1)
    return prompt, out


def latency_samples(reqs: List[Sent], t_end: float):
    """TTFT of every request (a request with no first token counts as the
    time it waited until ``t_end``) and every gap between tokens."""
    ttft, tbt = [], []
    for s in reqs:
        r = s.req
        if r.first_token_time is not None and not failed(r):
            ttft.append(r.first_token_time - s.due)
        else:
            ttft.append(max(t_end - s.due, 0.0))
        tbt.extend(np.diff(r.token_times).tolist())
    return ttft, tbt


# -------------------------------------------------------------------- check
def pick_sample(done: List[Sent], seed: int) -> List[Sent]:
    """The finished request with the most served tokens, then others in an
    order drawn from the seed, until the sample holds CHECK_REQUESTS
    requests and CHECK_TOKENS served tokens (or every finished request)."""
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.req.output), s.req.l_in))
    rest = [s for s in done if s is not longest]
    order = rng_for(seed, SAMPLE_STREAM).permutation(len(rest))
    sample, tokens = [longest], len(longest.req.output)
    for i in order:
        if len(sample) >= CHECK_REQUESTS and tokens >= CHECK_TOKENS:
            break
        sample.append(rest[i])
        tokens += len(rest[i].req.output)
    return sample


# ---------------------------------------------------------------------- run
@dataclass
class Window:
    w0: float
    w1: float
    t_end: float                # when the drain ended
    measured: List[Sent]        # requests due (open) or ended (closed) in it
    stages: List[Stage]         # stages that ended in it
    programs: int               # programs compiled or loaded in it
    prompt_tokens: int
    output_tokens: int
    traced: Optional[Tuple[float, float]] = None   # the profiled span


class Session:
    """Weights, engine and driver of one cell, kept across windows (the
    sweep and the control readings run several in one process)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, *,
                 rate: Optional[float] = None):
        self.cell, self.seconds = cell, seconds
        self.dims = dims_of(cell.config)
        self.dep = cell.config["deployment"]
        self.prog_cfg = program_config(cell.config, self.dims)
        self.counter = CompileCounter()
        self.rate = rate or cell.params.get("rate_per_s")
        self.params = None
        self.seed = None
        self.eng = make_engine(self.prog_cfg, None, self.dep)
        self.calls = StageCalls(self.eng)
        self.drv = Driver(self.eng)
        self.src = None
        self.set_seed(seed)

    def set_seed(self, seed: int):
        """Draw the seed's weights (freeing the last seed's first) and its
        traffic."""
        import jax
        self.eng.params = self.params = None
        gc.collect()
        t0 = time.monotonic()
        self.params = weights.program_params(seed, self.dims)
        jax.block_until_ready(self.params)
        self.eng.params = self.params
        log(f"[bench] weights of seed {seed} drawn in "
            f"{time.monotonic() - t0:.1f} s")
        self.seed = seed
        self.traffic = Traffic(self.cell.traffic, seed, self.dims.vocab,
                               self.seconds, rate=self.rate,
                               max_slots=self.dep["max_slots"])

    def _source(self, stream: int, base: float):
        if self.traffic.loop == "open":
            return OpenSource(self.traffic, stream, base)
        return ClosedSource(self.traffic, stream)

    def warm_up(self):
        """Drive the cell's traffic for ``virtual_s`` seconds of traffic
        time and ``stages`` stages (the mix's ``warmup``; either may be
        left out), so that the stages the window will run are compiled (or
        loaded from the persistent cache) before it starts. Traffic time
        advances by each step's wall time, except across a step that
        compiled or loaded a program, which counts as the median step: a
        stall does not pile up arrivals, and the warm-up meets the
        batches a steady window meets. Over its second half, each program
        also warms its neighbours (``StageCalls``). Its requests stay in
        flight, so the window starts in a steady state."""
        warm = self.cell.traffic["warmup"]
        v_goal = warm.get("virtual_s", 0)
        s_goal = warm.get("stages", 0)
        t_warm = time.monotonic()
        drv = self.drv
        self.src = self._source(WARM_STREAM, 0.0)
        vt, n, clean = 0.0, 0, []
        c_start = self.counter.snapshot()
        while vt < v_goal or n < s_goal:
            self.calls.neighbours = vt >= v_goal / 2 and n >= s_goal / 2
            nxt = self.src.feed(drv, vt, lambda k: "warm")
            c0 = self.counter.snapshot()
            t0 = time.monotonic()
            if not drv.step():
                vt = nxt if nxt is not None else vt + 0.01
                continue
            n += 1
            dt = time.monotonic() - t0
            c1 = self.counter.snapshot()
            if c1[0] == c0[0]:
                clean.append(dt)
            else:
                dt = float(np.median(clean[-50:])) if clean else 0.2
            vt += dt
        self.calls.neighbours = False
        c1 = self.counter.snapshot()
        log(f"[bench] warm-up: {n} stages, {vt:.1f} s of traffic in "
            f"{time.monotonic() - t_warm:.1f} s; "
            f"{programs_text(c_start, c1)}; {len(drv.live)} requests live")

    def window(self, trace_dir: Optional[str] = None,
               stream: int = WINDOW_STREAM) -> Window:
        """Measure for ``seconds``, with ``trace_dir`` under the profiler.
        An open loop's window holds the requests due in it, and the run
        keeps the load on until each is done (at most DRAIN_MAX_S); a
        closed loop's holds the requests that ended in it."""
        import jax
        drv, seconds = self.drv, self.seconds
        n0 = len(drv.sent)
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        known = set(self.calls.shapes)
        c0 = self.counter.snapshot()
        w0 = drv.clock()
        w1 = w0 + seconds
        if self.traffic.loop == "open":
            src = OpenSource(self.traffic, stream, w0)
            phase_of = lambda k: "window" if k == 0 else "after"  # noqa: E731
        else:
            src = self.src or ClosedSource(self.traffic, stream)
            phase_of = lambda k: "window"  # noqa: E731
        self.src = src
        span = (jax.profiler.TraceAnnotation("bench.window") if trace_dir
                else contextlib.nullcontext())
        with span:
            drive(drv, src, lambda now: now >= w1, phase_of)
        traced = (w0, drv.clock()) if trace_dir else None
        if trace_dir:
            jax.profiler.stop_trace()
        c1 = self.counter.snapshot()
        t_drain = t_end = drv.clock()
        if self.traffic.loop == "open":
            def pending():
                return [s for s in drv.sent[n0:]
                        if s.phase == "window" and not s.req.done]

            drive(drv, src, lambda now: (now >= t_drain + DRAIN_MAX_S
                                         or src.feed_done(w1)
                                         and not pending()),
                  phase_of)
            t_end = drv.clock()
            measured = [s for s in drv.sent[n0:] if s.phase == "window"]
        else:
            measured = [s for s in drv.sent
                        if s.ended is not None and w0 <= s.ended <= w1]
        prompt, out = window_tokens(drv.stages, drv.sent, w0, w1)
        win = Window(w0, w1, t_end, measured,
                     [st for st in drv.stages if w0 <= st.t1 <= w1],
                     c1[0] - c0[0], prompt, out, traced)
        log(f"[bench] window {seconds:.0f} s: {len(measured)} requests of "
            f"it, {len(win.stages)} stages; drained in "
            f"{t_end - t_drain:.1f} s; in the window {programs_text(c0, c1)}")
        new = [(kind, key) for kind, fns in (
                   ("mixed", self.eng._mixed_fns),
                   ("decode", self.eng._paged_decode_fns))
               for key, fn in fns.items()
               if fn in self.calls.shapes and fn not in known]
        if new:
            log(f"[bench] stage programs first called after the warm-up: "
                f"{new}")
        return win

    def finished_sample(self, win: Window):
        """(prompt, served tokens) of the requests the check compares, and
        how many served tokens fall outside the vocabulary."""
        sample = pick_sample([s for s in win.measured if s.req.completed],
                             self.seed)
        seqs = [(s.req.prompt, list(s.req.output)) for s in sample]
        oov = sum(1 for _, out in seqs for t in out
                  if not 0 <= t < self.dims.vocab)
        return seqs, oov

    def free(self):
        """Drop the program's state: requests, pages, weights, engine."""
        self.drv.cancel_all()
        self.eng = self.drv = self.src = self.params = None
        gc.collect()


def e2e_metrics(win: Window, seconds: float, setup_s: float) -> dict:
    ttft, tbt = latency_samples(win.measured, win.t_end)
    late = [s.sent - s.due for s in win.measured]
    log(f"[bench] tokens in window: {win.prompt_tokens} prompt + "
        f"{win.output_tokens} output; {len(ttft)} TTFT and {len(tbt)} TBT "
        f"samples")
    if ttft and tbt:
        log(f"[bench] TTFT p50 {1e3 * percentile(ttft, 50):.1f} ms, TBT p50 "
            f"{1e3 * percentile(tbt, 50):.1f} ms; generator late by p50 "
            f"{1e3 * percentile(late, 50):.2f} ms, max "
            f"{1e3 * max(late):.2f} ms")
    return {"setup_s": setup_s,
            "tokens_per_s": (win.prompt_tokens + win.output_tokens) / seconds,
            "ttft_p90_ms": 1e3 * percentile(ttft, 90) if ttft else None,
            "tbt_p99_ms": 1e3 * percentile(tbt, 99) if tbt else None}


def judge(gaps: np.ndarray, oov: int, params: dict, extra=None):
    """The checks of a run and whether it is correct: the widest and the
    99th-percentile gap of the compared tokens below the reference's best
    logit, each against the cell's limit; at least MIN_CHECKED tokens
    compared; none outside the vocabulary; and each of ``extra``
    ({name: count}) at 0."""
    n = len(gaps)
    checks = {
        "logit_gap": {"value": reference.widest(gaps),
                      "limit": params.get("logit_gap_limit")},
        "logit_gap_p99": {"value": float(np.percentile(gaps, 99))
                          if n else float("nan"),
                          "limit": params.get("logit_gap_p99_limit")},
        "tokens_checked": {"value": n, "limit": MIN_CHECKED},
        "tokens_outside_vocab": {"value": oov, "limit": 0}}
    for name, count in (extra or {}).items():
        checks[name] = {"value": count, "limit": 0}
    correct = (n >= MIN_CHECKED and all(
        c["limit"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"]
        for name, c in checks.items() if name != "tokens_checked"))
    return checks, bool(correct)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, trace_dir: Optional[str] = None,
             control: Optional[str] = None) -> dict:
    """One run. With ``control`` ("fp8", "int8"), the tokens compared are
    the control's first choices at the served positions in place of the
    served tokens: the same check has to find the run not correct."""
    import jax
    ses = Session(cell, seed, seconds)
    ses.warm_up()
    setup_s = time.monotonic() - t_process
    win = ses.window(trace_dir if trace else None)
    dims = ses.dims
    kernels = served_kernels(ses.eng, ses.calls.shapes) if trace else None
    ses.drv.cancel_all()
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    e2e = e2e_metrics(win, seconds, setup_s)
    unfinished = [s for s in win.measured
                  if not s.req.done or failed(s.req)]

    # ---- free the program's state, then the check
    seqs, oov = ses.finished_sample(win)
    ses.free()
    t_ref = time.monotonic()
    controls = (control,) if control else ()
    got = (reference.compare(seed, dims, seqs, controls) if seqs
           else {"tokens": 0, "gaps": np.zeros(0), control: np.zeros(0)})
    log(f"[bench] reference over {len(seqs)} requests, {got['tokens']} "
        f"served tokens, in {time.monotonic() - t_ref:.1f} s")
    gaps = got[control] if control else got["gaps"]

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": len(win.measured),
              "failed": len(unfinished), "metrics": {}, "device": device}
    extra = {}
    if not trace:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is None:
                raise ValueError(f"end-to-end metric {m['name']} has no "
                                 f"reading in this cell")
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from benchlib.devtrace import reduce_trace
        (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        t_red = time.monotonic()
        red = reduce_trace(path)
        t0, t1 = win.traced
        ctx = SimpleNamespace(
            cell=cell, dims=dims, window_s=t1 - t0,
            stages=[st for st in win.stages if t0 <= st.t1 <= t1],
            peaks=peaks(devices[0].device_kind), trace=red,
            window_programs=win.programs)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        unread = []
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is None:
                unread.append(m["name"])
            else:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
        lack = missing_kernels(kernels, dims.experts, ATTN_KERNEL,
                               HOT_KERNEL, COLD_KERNEL)
        log(f"[bench] trace of {red.window_s:.1f} s reduced in "
            f"{time.monotonic() - t_red:.1f} s; Pallas kernels in served "
            f"mixed-stage programs by k_cold: {kernels}; missing: "
            f"{lack or 'none'}; per-layer metrics with no reading: "
            f"{unread or 'none'}")
        extra = {"kernels_missing": sum(len(v) for v in lack.values())
                 + (0 if kernels else 1),
                 "per_layer_unread": len(unread)}
    checks, result["correct"] = judge(gaps, oov, cell.params, extra)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
