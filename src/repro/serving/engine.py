"""Continuous-batching serving engine with Duplex dispatch (C1–C3).

Stage loop (paper §II-C / §V, ROADMAP "DESIGN: chunked prefill"):

  * The scheduler forms a stage as one **unified token stream**: every
    active request contributes one decode token, and prefill work arrives as
    per-request *chunk spans* — with ``prefill_chunk_tokens`` set, a long
    prompt prefills across several stages (at most that many prompt tokens
    per stage) interleaved with everyone else's decode, so no prompt can
    stall decode TBT and the per-stage MoE token count stays near a constant
    target; ``prefill_chunk_tokens=None`` emits whole-prompt spans (legacy
    monolithic behavior) through the same machinery.
  * C1: ``core/dispatch.plan_stage`` computes each component's Op/B
    (decode, whole-prompt prefill, and chunk components — a chunk
    interpolates between the two as the budget shrinks) and selects its
    execution path.
  * C2: MoE layers run the *duplex* implementation over the WHOLE stage
    stream — decode rows and chunk rows are concatenated before routing, so
    with kernels on, the ragged scalar-prefetch path (live counts threaded,
    dead token blocks cost no DMAs or FLOPs) covers both halves. The
    planner's ``k_cold`` is chosen from an EMA of the *actual* per-expert
    router counts returned by the previous stage's step function
    (one-stage-stale statistics); padded batch rows are masked out of
    routing counts and expert capacity.
  * C3: decode rows run the bandwidth-path decode attention kernel; chunk
    rows run ``chunked_prefill_attention`` — queries attend the
    already-written KV prefix (paged: block-table-addressed, scalar-prefetch
    Pallas kernel or live-page-gather XLA fallback; dense: slot-row gather)
    plus the in-flight chunk. On Duplex hardware the two run concurrently on
    Logic-PIM/xPU; on a TPU they time-share the chip.

jit discipline: one mixed-stage step function per static key — (k_cold,
MoE capacities, chunk-row bucket, chunk-length bucket; paged additionally
decode-batch / live-page / chunk-page buckets) — so continuous batching
never recompiles in steady state. There is no separate monolithic prefill
function: an unchunked prompt is simply a whole-prompt chunk (a small
legacy prefill path survives only for architectures the unified stream
cannot serve yet — mamba / windowed / cross-attention mixers).

KV layouts: ``kv_layout="dense"`` decodes over all slots against the
``max_slots × max_len`` cache (seed behavior); ``kv_layout="paged"`` decodes
a gathered active-slot batch against a shared KV page pool, so per-stage HBM
traffic scales with occupancy × live context (docs/architecture.md). Chunk
rows address the same cache: dense chunks write their span into their slot's
row; paged chunks grow their block table (``ensure_len``) and write into
their pages.

Pages are refcounted and copy-on-write (PR 5): with ``prefix_share=True``,
prompts whose full-page token prefix is already resident map those pages at
refcount+1 and their chunk spans start at the first unshared position
(shared prefill stages are skipped outright; a shared page is
copied-on-write before any scatter targets it). With
``preemption="recompute"``, paged pools may be oversubscribed
(``kv_num_pages`` below worst case): when the next stage's growth would
exhaust the pool, the lowest-priority request's pages are decref'd — shared
pages survive under their other owners — and it replays through the
recompute path. Accounting (``kv_bytes_streamed``, ``live_pages``) counts a
shared page once. The kernels need no changes: block tables already
indirect every access.

Async pipelining (PR 8, docs/architecture.md "Async serving loop"): the
stage loop is split into ``plan_stage`` (pure host: maintenance, admission
caps, scheduler spans, Op/B planning — no device sync), ``dispatch_stage``
(host KV growth + input staging + the jitted enqueue; returns a
:class:`StageFuture` holding device arrays) and ``commit_stage`` (the ONLY
point that materializes tokens via ``np.asarray`` and advances durable
state — ``kv.lens``, sampled outputs, scheduler positions). ``step()``
composes the three synchronously (behavior and chaos draw order identical
to the pre-split engine); ``run_async()`` pipelines them — while stage N
executes on device, the host speculatively plans stage N+1 from the
*projected* post-commit state, and stage N−1's accounting (router-count
EMA, traffic model, report, audit) is deferred until after stage N+1's
dispatch. A commit that contradicts the prediction (an EOS finish, a
cancel, an eviction, an expiry) invalidates the speculative plan and the
engine re-plans from real state — speculation affects only the overlap,
never the tokens. ``submit``/``cancel``/``stats`` are lock-guarded so a
fleet poller (or a client thread) is safe against the loop.

Spans (``serving/tracing.py``, on the profiler's clock): ``engine.step``
(with the stage index) and ``engine.turn`` around the two loops' units;
inside them ``engine.plan`` (children ``.maintain``, ``.admit``, ``.draft``,
``.schedule``, ``.duplex``), ``engine.dispatch`` (``engine.dispatch.inputs``,
``engine.launch``), ``engine.sync``, ``engine.commit`` and
``engine.account``; and one ``engine.queue`` per admission, from the
request's entry into the queue to its KV slot.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, ATTN_LOCAL, MAMBA, MOE, ModelConfig
from repro.core.costmodel import DUPLEX
from repro.core.dispatch import plan_stage as core_plan_stage
from repro.core.execution import (ExecutionPlan, execution_plan,
                                  moe_lowering_tally)
from repro.core.partition import DuplexPlanner, build_luts
from repro.models.model import decode_step, init_cache, mixed_step, prefill
from repro.serving import tracing
from repro.serving.drafter import NgramDrafter
from repro.serving.faults import (FaultInjector, InjectedFault,
                                  InjectedStepError)
from repro.serving.kvmanager import KVManager
from repro.serving.request import Request, RequestState
from repro.serving.sampling import SamplingParams, sample
from repro.serving.scheduler import (AdmissionRejected,
                                     ContinuousBatchingScheduler,
                                     StageDecision)


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _pow2_buckets(n_max: int) -> Tuple[int, ...]:
    out = []
    b = 1
    while b < n_max:
        out.append(b)
        b *= 2
    out.append(n_max)
    return tuple(out)


def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _select_tokens(prev_nxt, prev_cn, src_nxt, src_cn, fallback, mode):
    """Assemble a chained stage's decode input tokens ON DEVICE from the
    previous stage's (not yet materialized) sampled-token futures: row i
    takes ``prev_nxt[src_nxt[i]]`` / ``prev_cn[src_cn[i]]`` when the
    source index is >= 0, else the host-known ``fallback[i]``. Traced
    into the chained stage step (:func:`_chain_fn`), this is what lets
    stage N+1 dispatch before stage N finishes — the host never touches
    the token values. ``mode`` (static, see :meth:`ChainInfo.mode`)
    elides the gathers a stage provably doesn't need."""
    flat_n = prev_nxt.reshape(-1)
    if mode == "pure":
        return flat_n[src_nxt][:, None].astype(jnp.int32)
    t = jnp.where(src_nxt >= 0, flat_n[jnp.maximum(src_nxt, 0)], fallback)
    if mode == "full":
        flat_c = prev_cn.reshape(-1)
        t = jnp.where(src_cn >= 0, flat_c[jnp.maximum(src_cn, 0)], t)
    return t[:, None].astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _chain_fn(fn, mode="full"):
    """The chained variant of a jitted stage step: same computation, but
    the decode input tokens (always the step's SECOND argument, across
    every stage family) are assembled on device by :func:`_select_tokens`
    from the previous stage's output futures. One fused jit call — a
    chained stage costs the same number of kernel launches as a sync
    one. ``jax.jit`` drops the args an elided gather leaves unused."""
    @jax.jit
    def chained(params, prev_nxt, prev_cn, src_nxt, src_cn, fallback,
                *rest):
        toks = _select_tokens(prev_nxt, prev_cn, src_nxt, src_cn, fallback,
                              mode)
        return fn(params, toks, *rest)
    return chained


@dataclass
class StageReport:
    stage_index: int
    is_mixed: bool
    num_decode: int
    num_prefill: int            # prefill-chunk rows this stage
    k_cold: int
    bandwidth_flop_fraction: float
    # K+V bytes the attention paths stream this stage (all attention
    # layers). Dense: max_slots × max_len regardless of occupancy (+ chunk
    # slot-row gathers). Paged: live pages of the active decode slots plus
    # each chunk's prefix+chunk pages.
    kv_bytes_streamed: int = 0
    # MoE weight+activation bytes the stage's expert kernels stream (all MoE
    # layers, modeled from the stage's ACTUAL per-expert router counts as
    # returned by the jitted step). Padded kernels execute the full capacity
    # grid; ragged kernels execute live token blocks only.
    moe_bytes_streamed: int = 0
    moe_flops_live: int = 0       # FLOPs over live (routed) token blocks
    moe_flops_padded: int = 0     # FLOPs the capacity-padded path would burn
    # live prefill-chunk tokens this stage / total live tokens through the
    # MoE stream (decode + chunk) — the quantity chunking stabilizes
    chunk_tokens: int = 0
    stage_tokens: int = 0
    # pages mapped by >1 owner after this stage (paged + prefix_share);
    # kv_bytes_streamed already counts each unique page once
    shared_kv_pages: int = 0
    # robustness counters (PR 6): per-stage deltas of the engine totals.
    # ``aborted`` marks a stage unwound by an injected fault — its
    # admissions returned to the queue head and nothing advanced.
    aborted: bool = False
    shed: int = 0
    expired: int = 0
    cancelled: int = 0
    retries: int = 0
    audit_violations: int = 0
    # speculative decoding (PR 9): draft tokens this stage's verify spans
    # carried / draft tokens the verifier's argmax agreed with (the bonus
    # token every verify row commits on top is not counted — acceptance
    # rate is spec_accepted / spec_proposed, and a rate of r means each
    # verify row committed r·k + 1 tokens for one stage's latency).
    spec_proposed: int = 0
    spec_accepted: int = 0


@dataclass
class ChainInfo:
    """Device-side token chaining for a speculative stage N+1 that is
    dispatched BEFORE stage N materializes (the async loop's zero-gap fast
    path). The only true data dependency between consecutive stages is the
    sampled token values; everything else in N+1's inputs is projectable
    on the host. ``src_nxt``/``src_cn`` map each of N+1's decode input
    rows to the row of N's ``nxt``/``cn`` device array that feeds it (−1 =
    no dependency, use the host-known ``fallback`` token), and a tiny
    jitted gather assembles the token array ON DEVICE, chained on N's
    futures — so N+1 enqueues while N is still executing and the device
    never idles. ``proj_lens`` holds each decode slot's projected
    post-commit-N length (what ``kv.lens`` will say once N commits),
    which input staging reads instead of the not-yet-advanced real
    lengths."""
    src_nxt: np.ndarray              # per input row: index into N's nxt, -1
    src_cn: np.ndarray               # per input row: index into N's cn, -1
    fallback: np.ndarray             # per input row: host-known token value
    prev_nxt: Any                    # stage N's nxt device future
    prev_cn: Any                     # stage N's cn device future (or dummy)
    proj_lens: Dict[int, int]        # slot -> projected pre-write length

    @property
    def mode(self) -> str:
        """Static gather shape for :func:`_chain_fn` specialization:
        ``pure`` = every row reads N's ``nxt`` (plain gather, no chunk
        sources, no fallback), ``nxt_only`` = no chunk sources, ``full``
        = both gathers. Host-known at dispatch, so the unused gather is
        never traced (and its source array never transferred)."""
        if (self.src_cn >= 0).any():
            return "full"
        return "pure" if (self.src_nxt >= 0).all() else "nxt_only"

    def wrap(self, fn, params):
        """``fn`` behind the on-device token gather, and its leading
        arguments: the gather's inputs take the place of the token
        array."""
        return _chain_fn(fn, self.mode), (params, self.prev_nxt,
                                          self.prev_cn, self.src_nxt,
                                          self.src_cn, self.fallback)


@dataclass
class StagePlan:
    """A formed-but-not-yet-dispatched stage (PR 8). ``speculative`` plans
    were built against the PROJECTED post-commit state of an in-flight
    stage (scheduler state untouched — ``activate`` runs at dispatch);
    ``epoch`` pins the engine mutation epoch the plan assumed, so any
    out-of-band submit/cancel/evict/expiry invalidates it. A plan with a
    ``chain`` dispatches before its predecessor's sync point (see
    :class:`ChainInfo`)."""
    decision: StageDecision
    k_cold: int
    splan: Optional[Any]
    snap: Tuple[int, int, int, int]  # (shed, expired, cancelled, retries)
    tnow: float = 0.0               # engine clock tokens are recorded at
    speculative: bool = False
    epoch: int = -1
    chain: Optional[ChainInfo] = None


@dataclass
class StageFuture:
    """An in-flight dispatched stage: device arrays (JAX futures) plus the
    host-side context ``commit_stage`` needs to apply them. Nothing durable
    — ``kv.lens``, sampled tokens, scheduler positions — has advanced yet;
    dropping a future (replica kill) abandons device work but corrupts no
    host state."""
    plan: StagePlan
    nxt: Any = None                 # decode next-token device array
    cn: Any = None                  # chunk next-token device array
    cn_all: Any = None              # per-position chunk argmax (spec verify)
    counts: Any = None              # summed per-expert router counts
    legacy_nxt: Any = None          # legacy monolithic prefill next tokens
    legacy_cache: Any = None        # legacy local cache (scattered at commit)
    kv_bytes: int = 0
    moe_caps: Optional[Tuple[int, int, int]] = None
    # per-stage robustness-counter deltas, frozen by ``_commit_critical`` so
    # the deferred report can't absorb the NEXT stage's window
    deltas: Tuple[int, int, int, int] = (0, 0, 0, 0)
    # speculative decoding (PR 9): per-stage draft/accept counts frozen at
    # the critical commit for the deferred StageReport
    spec_proposed: int = 0
    spec_accepted: int = 0
    # (rid, token) pairs committed this stage, in commit order — the
    # deferred commit fires ``on_token`` callbacks from here, OFF the
    # critical section (only populated when a callback is registered)
    emitted: List[Tuple[int, int]] = field(default_factory=list)


class EngineStalledError(RuntimeError):
    """``engine.run()``'s watchdog: raised instead of silently spinning when
    no stage can make progress (capacity livelock, a fault schedule that
    never relents, or an exhausted stage/wall budget). The message lists the
    stuck request ids, queue depth and free capacity so the operator can
    tell livelock from overload at a glance."""


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_slots: int,
                 max_len: int, use_duplex: bool = True,
                 use_kernels: bool = False, kv_quant: bool = False,
                 kv_dtype: Optional[str] = None,
                 moe_ragged: bool = True, moe_c_block: int = 256,
                 preemption: str = "none", kv_layout: str = "dense",
                 kv_page_size: int = 64, kv_num_pages: Optional[int] = None,
                 prefix_share: bool = False,
                 sampling: SamplingParams = SamplingParams(),
                 max_prefill_seqs: int = 4, max_prefill_tokens: int = 8192,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefill_len_buckets: Tuple[int, ...] = (64, 128, 256, 512,
                                                         1024, 2048, 4096),
                 queue_cap: Optional[int] = None,
                 overload_policy: str = "reject",
                 aging_rounds: Optional[int] = None,
                 injector: Optional[FaultInjector] = None,
                 audit_stages: Optional[bool] = None,
                 spec_k: int = 0, spec_ngram: int = 3,
                 on_token: Optional[Callable[[int, int], None]] = None,
                 seed: int = 0):
        assert not cfg.is_encoder_decoder, \
            "engine serves decoder-only LMs; enc-dec is exercised via serve_step"
        assert preemption in ("none", "migrate", "recompute")
        self.preemption = preemption
        self.preemptions = 0
        self.cfg = cfg
        self.params = params
        # fault injection + auditing (PR 6): the injector threads into the
        # KV manager (page-alloc failures) and the stage loop (step errors,
        # forced evictions, latency spikes). Auditing after every stage
        # defaults on exactly when chaos is on.
        self.injector = injector
        self.audit_stages = (injector is not None if audit_stages is None
                             else bool(audit_stages))
        # kv_dtype overrides the cache storage dtype (e.g. a bf16 KV cache
        # under fp32 compute); kv_quant=True stores int8 + fp32 scales and
        # wins over kv_dtype for the value pools.
        self.kv = KVManager(cfg, max_slots, max_len, dtype=kv_dtype,
                            kv_quant=kv_quant, layout=kv_layout,
                            page_size=kv_page_size, num_pages=kv_num_pages,
                            injector=injector)
        self.paged = self.kv.paged
        if self.paged and preemption == "migrate":
            raise NotImplementedError(
                "migrate gathers dense slot rows to host; paged preemption "
                "uses the recompute-replay path (preemption='recompute')")
        if prefix_share and not self.paged:
            raise ValueError(
                "prefix_share needs kv_layout='paged' (sharing maps "
                "refcounted pages between block tables)")
        self.prefix_share = bool(prefix_share)
        # prefill positions skipped because their KV was already resident
        # (shared-prefix admissions + post-eviction replays that re-matched)
        self.shared_tokens_skipped = 0
        self.peak_active = 0
        # the unified token-stream stage covers full self-attention decoder
        # stacks; mamba needs cross-chunk state carry and ring (ATTN_LOCAL)
        # caches overwrite prefix slots mid-chunk (ROADMAP open items) —
        # those archs keep the legacy monolithic prefill path.
        self._unified = all(kind.mixer == ATTN
                            for seg in cfg.segments for kind in seg.pattern)
        if prefill_chunk_tokens is not None and not self._unified:
            raise NotImplementedError(
                "chunked prefill needs a full self-attention decoder stack "
                "(mamba/windowed/cross mixers still prefill monolithically)")
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.scheduler = ContinuousBatchingScheduler(
            max_prefill_seqs=max_prefill_seqs,
            max_prefill_tokens=max_prefill_tokens,
            prefill_chunk_tokens=prefill_chunk_tokens,
            max_prefill_target=max_len,
            queue_cap=queue_cap, overload_policy=overload_policy,
            aging_rounds=aging_rounds)
        # robustness counters (PR 6) — engine lifetime totals; StageReport
        # carries the per-stage deltas and stats() the roll-up.
        self.cancelled = 0
        self.expired = 0
        self.shed = 0
        self.rejected = 0
        self.retries = 0
        self.stage_aborts = 0
        self.forced_evictions = 0
        self.audit_violations = 0
        self.audit_log: List[str] = []
        # stats(reset=True) snapshot base (PR 7): counter values at the last
        # reset, so a fleet aggregator can attribute sheds/retries/etc. to a
        # polling window instead of re-diffing cumulative totals itself.
        self._stats_base: Dict[str, int] = {}
        # accumulated virtual latency (injected spikes + retry backoff);
        # added to every clock read so deadlines feel the slowdown without
        # the test suite actually sleeping
        self.fault_delay = 0.0
        # every submitted request, by rid — cancel() needs to find queued /
        # running / already-finished requests uniformly
        self._requests: Dict[int, Request] = {}
        self.sampling = sampling
        self.use_duplex = use_duplex and cfg.moe is not None
        self.use_kernels = use_kernels
        # ragged MoE kernels need the count-threaded duplex path + Pallas
        # (the XLA grouped fallback is inherently capacity-padded).
        self.moe_ragged = bool(moe_ragged and use_kernels and self.use_duplex)
        self.moe_c_block = moe_c_block
        # legacy monolithic prefill buckets (non-unified archs only);
        # max_len is always a bucket so no prompt within KV capacity is
        # silently truncated.
        self.prefill_len_buckets = tuple(sorted(
            {b for b in prefill_len_buckets if b < max_len} | {max_len}))
        # chunk-row jit buckets: prefill admissions are capped at
        # max_prefill_seqs, but with spec decoding (PR 9) every decode row
        # may additionally carry a verify span — the row bucket must cover
        # max_prefill_seqs + max_slots without per-count recompiles
        row_cap = max_prefill_seqs + (max_slots if spec_k > 0 else 0)
        self.seq_buckets = tuple(sorted(
            {1, 2, max_prefill_seqs, row_cap} | set(_pow2_buckets(row_cap))))
        # chunk-length jit buckets: powers of two up to the chunk budget
        # (or max_len for whole-prompt spans)
        self.chunk_len_buckets = _pow2_buckets(
            min(prefill_chunk_tokens, max_len) if prefill_chunk_tokens
            else max_len)
        self.planner: Optional[DuplexPlanner] = None
        if self.use_duplex:
            # the xPU LUT models what the hot kernel executes: ragged →
            # block-quantized live tokens; padded → the full capacity grid,
            # weights re-streamed once per c_block token block either way.
            ch, _, cb = self._moe_caps(max_slots, 0)
            if self.moe_ragged:
                hot_kw = dict(hot_block=cb)
            else:
                hot_kw = dict(hot_block=cb, hot_capacity=ch)
            max_stage_tokens = (max(4 * max_slots, 512)
                                + max_prefill_seqs * self.chunk_len_buckets[-1])
            lut_x, lut_p = build_luts(DUPLEX, cfg.d_model,
                                      cfg.moe.d_ff_expert,
                                      max_tokens=max_stage_tokens,
                                      **hot_kw)
            self.planner = DuplexPlanner(lut_x, lut_p, cfg.moe.num_experts)
        # EMA of per-MoE-layer per-expert router counts, harvested from each
        # stage's jitted step (ROADMAP open item: actual counts, not a
        # synthetic multinomial draw, drive the planner + traffic model).
        self._ema_counts: Optional[np.ndarray] = None
        self._count_ema_decay = 0.5
        # decode-attention streamed-bytes accounting (K+V only; mamba mixers
        # hold O(1) state and cross-attn KV is written once, both excluded).
        # Dense streams each layer's whole buffer — max_len for full
        # attention, the ring (window+1) for ATTN_LOCAL. Bytes reflect the
        # ACTUAL cache dtype: int8 caches stream 1-byte values plus their
        # fp32 per-(token, kv-head) scales, not the compute dtype.
        from repro.serving.kvmanager import kv_token_bytes
        per_tok = kv_token_bytes(cfg, kv_quant=kv_quant, dtype=kv_dtype)
        n_attn = 0
        dense_tokens_per_slot = 0
        for seg in cfg.segments:
            for kind in seg.pattern:
                if kind.mixer == MAMBA:
                    continue
                n_attn += seg.repeats
                if kind.mixer == ATTN_LOCAL and cfg.sliding_window > 0:
                    dense_tokens_per_slot += seg.repeats * (
                        min(max_len, cfg.sliding_window) + 1)
                else:
                    dense_tokens_per_slot += seg.repeats * max_len
        self._kv_bytes_per_token = per_tok * n_attn
        self._dense_kv_bytes_per_stage = (max_slots * per_tok *
                                          dense_tokens_per_slot)
        # MoE streamed-bytes accounting: layer count + GEMM matrices per
        # expert FFN (3 SwiGLU / 2 classic) for the traffic model.
        self._moe_layers = sum(seg.repeats
                               for seg in cfg.segments
                               for kind in seg.pattern if kind.ffn == MOE)
        self._moe_mats = 3 if cfg.gated_ffn else 2
        self._param_itemsize = jnp.dtype(cfg.param_dtype).itemsize
        self._key = jax.random.PRNGKey(seed)
        self._tokens = np.zeros((max_slots,), np.int32)   # last token per slot
        self._slot_req: Dict[int, Request] = {}
        self._decode_fns: Dict[Tuple, callable] = {}
        self._paged_decode_fns: Dict[Tuple, callable] = {}
        self._mixed_fns: Dict[Tuple, callable] = {}
        # per stage program: how its MoE layers lowered when it was traced
        # (``moe_lowering_tally``), summed by ``stats()``
        self._moe_lowered: Dict[Tuple, Any] = {}
        self._legacy_prefill_fns: Dict[Tuple[int, int], callable] = {}
        # paged jit keys: (batch bucket, live-page bucket) — powers of two
        # so steady-state continuous batching never recompiles.
        self.decode_bs_buckets = _pow2_buckets(max_slots)
        if self.paged:
            self.pages_buckets = _pow2_buckets(self.kv.max_pages_per_slot)
        self._stage_idx = 0
        self.reports: List[StageReport] = []
        # ---- async pipelining (PR 8) ----
        # one re-entrant lock guards every host-state mutation: client
        # threads' submit()/cancel(), the loop's plan/dispatch/commit, and
        # stats() windows a fleet poller reads from another thread (the
        # saxml servable_model StepCounter idiom). Device syncs
        # (np.asarray) happen OUTSIDE the lock so a submit never blocks
        # behind device compute.
        self._lock = threading.RLock()
        # mutation epoch: bumped by every out-of-band state change a
        # speculative plan could not have predicted (submit, cancel/shed/
        # expiry, eviction). Dispatch-time validation compares epochs —
        # cheaper than diffing scheduler state.
        self._epoch = 0
        self._inflight: Optional[StageFuture] = None   # step_async() only
        # host stage-gap accounting: from the end of a stage's
        # ``engine.sync`` span to the end of the NEXT stage's last
        # ``engine.launch`` — the window the device sits idle waiting on
        # the host. The async loop exists to drive this toward zero.
        self._t_sync_done: Optional[float] = None
        self._t_launched = 0.0
        self.host_gap_s = 0.0
        self.gap_stages = 0
        self.spec_hits = 0      # speculative plans dispatched as-is
        self.spec_misses = 0    # invalidated at commit -> re-planned
        self.spec_miss_reasons: Dict[str, int] = {}
        self.chained_stages = 0  # dispatched BEFORE the previous sync point
        # double-buffered input staging: two reusable host buffer sets
        # alternate per dispatch, so building stage N+1's inputs never
        # touches arrays stage N's transfer read (the jitted call snapshots
        # host buffers at enqueue, so this is belt-and-braces; the
        # measurable win is zero per-stage allocation churn on the hot
        # path).
        self._staging_bufs: List[Dict[str, np.ndarray]] = [{}, {}]
        self._staging_idx = 0
        # ---- speculative decoding (PR 9) ----
        # spec_k > 0 turns on self-speculative decode: an n-gram drafter
        # proposes up to spec_k tokens per decode row and the scheduler
        # emits them as verify ChunkSpans through the SAME mixed-stage
        # path (serving/drafter.py has the full contract). Greedy-only:
        # acceptance compares the verifier's argmax against the draft,
        # which reproduces the unspeculated greedy stream exactly.
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.drafter: Optional[NgramDrafter] = None
        if self.spec_k > 0:
            if sampling.temperature > 0.0:
                raise ValueError(
                    "speculative decoding requires greedy sampling "
                    "(temperature == 0): acceptance compares the "
                    "verifier's argmax against the draft — sampled "
                    "decoding would need rejection sampling to keep the "
                    "output distribution")
            if not self._unified:
                raise NotImplementedError(
                    "speculative decoding rides the unified mixed-stage "
                    "chunk path (full self-attention decoder stacks only)")
            self.drafter = NgramDrafter(k=self.spec_k, ngram=self.spec_ngram)
        self.spec_proposed = 0   # draft tokens sent to verification
        self.spec_accepted = 0   # draft tokens the verifier agreed with
        self.spec_rewinds = 0    # verify rows that rolled KV back
        # streaming (PR 9 satellite): per-token callback, fired from the
        # DEFERRED commit half — after the next stage's dispatch in the
        # async loops — so a slow consumer can never stall the pipeline.
        self.on_token = on_token

    # ------------------------------------------------------------------ jits
    def _moe_caps(self, T: int, k_cold: int) -> Tuple[int, int, int]:
        """(c_hot, c_cold, c_block) for a stage of T (already bucketed,
        padding included) tokens. The hot capacity snaps up to a power-of-two
        count of c_block-sized token blocks — the stage's *live-block
        bucket* — so the ragged kernel's token-block grid is a stable jit
        key and steady state never recompiles."""
        from repro.core.duplex_moe import default_capacities
        if self.cfg.moe is None:
            return 0, 0, self.moe_c_block
        ch, cc = default_capacities(T, self.cfg.moe, k_cold)
        cb = min(self.moe_c_block, _pow2_ceil(ch))
        blocks = _pow2_ceil(-(-ch // cb))
        return blocks * cb, cc, cb

    def _moe_plan(self, k_cold: int, c_hot: int, c_cold: int,
                  c_block: int) -> ExecutionPlan:
        # the ragged kernels live on the count-threaded duplex path, so keep
        # it selected even at k_cold == 0 (all experts hot, all ragged).
        use_duplex_impl = k_cold > 0 or self.moe_ragged
        return ExecutionPlan(
            moe_impl="duplex" if use_duplex_impl else "grouped",
            k_cold=k_cold,
            c_hot=c_hot if use_duplex_impl else None,
            c_cold=c_cold if use_duplex_impl else None,
            moe_ragged=self.moe_ragged, moe_c_block=c_block,
            use_kernels=self.use_kernels)

    def execution_plan_for(self, n_tokens: int,
                           k_cold: int = 0) -> ExecutionPlan:
        """The plan a stage of ``n_tokens`` (bucketed, padding included)
        tokens at ``k_cold`` is traced under — for checking the kernel path
        against ``dataclasses.replace(plan, use_kernels=False)``."""
        return self._moe_plan(k_cold, *self._moe_caps(n_tokens, k_cold))

    @contextlib.contextmanager
    def _tracing_stage(self, plan: ExecutionPlan, key: Tuple):
        """The context a stage function's body is traced in: its plan, and
        the tally of how its MoE layers lower, kept under ``key``."""
        with execution_plan(plan), moe_lowering_tally() as tally:
            yield
        self._moe_lowered[key] = tally

    def _decode_fn(self, k_cold: int, c_hot: int, c_cold: int, c_block: int):
        key = (k_cold, c_hot, c_cold)
        if key not in self._decode_fns:
            cfg = self.cfg
            plan = self._moe_plan(k_cold, c_hot, c_cold, c_block)
            stage = ("decode",) + key

            @jax.jit
            def fn(params, tokens, valid, cache, key):
                with self._tracing_stage(plan, stage):
                    logits, new_cache, counts = decode_step(
                        params, cfg, tokens, cache,
                        attn_ctx={"valid": valid}, return_moe_counts=True)
                nxt = sample(logits, key, self.sampling)
                return nxt, new_cache, counts

            self._decode_fns[key] = fn
        return self._decode_fns[key]

    def _paged_decode_fn(self, k_cold: int, c_hot: int, c_cold: int,
                         c_block: int, n_batch: int, n_pages: int):
        """Paged decode step over a gathered active-slot batch. Static key =
        (k_cold, hot/cold capacities, batch bucket, live-page bucket): both
        the kv grid and the MoE token-block grid are trimmed to the stage's
        bucketed live work, not the configured maxima."""
        key = (k_cold, c_hot, c_cold, n_batch, n_pages)
        if key not in self._paged_decode_fns:
            cfg = self.cfg
            plan = self._moe_plan(k_cold, c_hot, c_cold, c_block)

            @jax.jit
            def fn(params, tokens, cache, lengths, block_tables, key_):
                with self._tracing_stage(plan, ("paged_decode",) + key):
                    logits, new_cache, counts = decode_step(
                        params, cfg, tokens, cache,
                        attn_ctx={"lengths": lengths,
                                  "block_tables": block_tables,
                                  "valid": lengths > 0},
                        return_moe_counts=True)
                nxt = sample(logits, key_, self.sampling)
                return nxt, new_cache, counts

            self._paged_decode_fns[key] = fn
        return self._paged_decode_fns[key]

    def _mixed_fn(self, k_cold: int, c_hot: int, c_cold: int, c_block: int,
                  n_chunks: int, chunk_len: int, n_batch: int = 0,
                  n_pages: int = 0, n_cpages: int = 0, spec: bool = False):
        """The unified mixed-stage step: decode rows + chunk rows through
        one traced model call (``models/model.py::mixed_step``) whose MoE
        layers see the concatenated token stream. Static key = (k_cold,
        capacities, chunk-row bucket, chunk-length bucket; paged: + decode
        batch / live-page / chunk-page buckets). ``spec`` (PR 9) keys the
        speculative-verify variant: the model additionally returns the
        greedy argmax at EVERY chunk position (``cn_all``), which the
        commit compares against each verify span's draft to find the
        accepted prefix."""
        key = (k_cold, c_hot, c_cold, n_chunks, chunk_len,
               n_batch, n_pages, n_cpages, spec)
        if key not in self._mixed_fns:
            cfg = self.cfg
            plan = self._moe_plan(k_cold, c_hot, c_cold, c_block)

            if self.paged:
                @jax.jit
                def fn(params, dec_tokens, dec_lengths, dec_bt, chunk_tokens,
                       starts, clens, chunk_bt, cache, key_):
                    with self._tracing_stage(plan, ("mixed",) + key):
                        out = mixed_step(
                            params, cfg, dec_tokens, chunk_tokens, cache,
                            attn_ctx={"lengths": dec_lengths,
                                      "block_tables": dec_bt,
                                      "valid": dec_lengths > 0},
                            chunk_ctx={"starts": starts,
                                       "chunk_lens": clens,
                                       "block_tables": chunk_bt},
                            spec_tokens=spec)
                    dl, cl, new_cache, counts = out[:4]
                    kd, kc = jax.random.split(key_)
                    nxt = sample(dl, kd, self.sampling)
                    cn = sample(cl, kc, self.sampling)
                    if spec:
                        return nxt, cn, out[4], new_cache, counts
                    return nxt, cn, new_cache, counts
            else:
                @jax.jit
                def fn(params, dec_tokens, dec_valid, chunk_tokens, slots,
                       starts, clens, cache, key_):
                    with self._tracing_stage(plan, ("mixed",) + key):
                        out = mixed_step(
                            params, cfg, dec_tokens, chunk_tokens, cache,
                            attn_ctx={"valid": dec_valid},
                            chunk_ctx={"slots": slots, "starts": starts,
                                       "chunk_lens": clens},
                            spec_tokens=spec)
                    dl, cl, new_cache, counts = out[:4]
                    kd, kc = jax.random.split(key_)
                    nxt = sample(dl, kd, self.sampling)
                    cn = sample(cl, kc, self.sampling)
                    if spec:
                        return nxt, cn, out[4], new_cache, counts
                    return nxt, cn, new_cache, counts

            self._mixed_fns[key] = fn
        return self._mixed_fns[key]

    def _legacy_prefill_fn(self, n_seqs: int, seq_len: int):
        """Monolithic whole-prompt prefill into a fresh local cache —
        retained only for archs the unified stream cannot serve (mamba /
        windowed / cross mixers); full-attention stacks never come here."""
        key = (n_seqs, seq_len)
        if key not in self._legacy_prefill_fns:
            cfg = self.cfg
            max_len = self.kv.max_len
            plan = ExecutionPlan(moe_impl="grouped",
                                 use_kernels=self.use_kernels)
            kv_quant = self.kv.kv_quant

            @jax.jit
            def fn(params, tokens, true_len, skey):
                with execution_plan(plan):
                    cache = init_cache(cfg, n_seqs, max_len,
                                       kv_quant=kv_quant)
                    logits, new_cache = prefill(params, cfg,
                                                {"tokens": tokens}, cache,
                                                true_len)
                nxt = sample(logits, skey, self.sampling)
                return nxt, new_cache

            self._legacy_prefill_fns[key] = fn
        return self._legacy_prefill_fns[key]

    # ------------------------------------------------------------------ api
    def _now(self, now: Optional[float] = None) -> float:
        """The engine clock: caller-supplied virtual time (benchmarks) or
        wall time, plus the accumulated injected latency, so deadlines and
        SLOs feel chaos-mode slowdowns without anyone sleeping."""
        return (now if now is not None else time.monotonic()) + self.fault_delay

    def submit(self, req: Request, now: Optional[float] = None) -> None:
        """Admit ``req`` to the scheduler. Raises :class:`AdmissionRejected`
        when the bounded queue is full of live work (policy ``reject``, or
        ``shed-past-deadline`` with nothing expired); under the shedding
        policies the displaced victims are finished with reason ``"shed"``
        and their resources (queued-head prefix pins included) released.
        Admission runs BEFORE prefix matching so a rejected request can
        never leak a pin."""
        if req.l_in >= self.kv.max_len:
            raise ValueError(
                f"prompt of {req.l_in} tokens cannot fit max_len="
                f"{self.kv.max_len} KV (plus at least one generated token); "
                f"raise max_len — prompts are never silently truncated")
        with self._lock:
            tnow = self._now(now)
            try:
                shed = self.scheduler.submit(req, now=tnow)
            except AdmissionRejected:
                self.rejected += 1
                raise
            for victim in shed:
                self._finish_abnormal(victim, "shed", tnow)
            req.queued_at = time.monotonic()
            self._requests[req.rid] = req
            self._match_prefix(req)
            self._epoch += 1            # invalidates any speculative plan

    def cancel(self, rid: int, now: Optional[float] = None) -> bool:
        """Cancel a request by id, wherever it is in its lifecycle: dropped
        from the queue (releasing any queued-head prefix pins), or pulled
        out of prefill/decode with its slot and pages freed. Returns False
        for unknown or already-terminal requests. Takes effect between
        stages — an in-flight stage's work for the request is discarded at
        its next admission check."""
        with self._lock:
            req = self._requests.get(rid)
            if req is None or req.done:
                return False
            self._finish_abnormal(req, "cancelled", self._now(now))
            return True

    def _finish_abnormal(self, req: Request, reason: str,
                         tnow: float) -> None:
        """Terminal path for cancel / shed / expiry: detach ``req`` from the
        scheduler and release every resource it holds — its KV slot (paged:
        decref its pages; shared prefixes survive under their other owners),
        its queued-head prefix pins, and any host-saved migrated cache."""
        self.scheduler.remove(req)
        if req.slot >= 0:
            self.kv.free(req.slot)
            self._slot_req.pop(req.slot, None)
            req.slot = -1
        if req.shared_pages:
            # the satellite-1 leak: a never-admitted request's pins were
            # previously unreleasable — unpin here so the pool drains to
            # fully-free no matter where in the lifecycle the request died
            self.kv.unpin(req.shared_pages)
            req.shared_pages = None
        req.saved_cache = None
        req.finish(reason, tnow)
        self._epoch += 1                # invalidates any speculative plan
        if reason == "expired":
            self.expired += 1
        elif reason == "shed":
            self.shed += 1
        else:
            self.cancelled += 1

    def _match_prefix(self, req: Request) -> None:
        """Prefix sharing: match the request's full-page token prefix
        against resident pages and pin the hits, so they survive the queue
        wait. ``prefill_pos`` moves to the first unshared position — capped
        at target-1 so the final position is always processed (the engine
        samples the first token from its logits; its page, shared, is
        copied-on-write before the write). Idempotent and monotonic: called
        at submit AND again while queued (the index grows as earlier
        admissions prefill), it only ever upgrades to a longer match,
        releasing the shorter pin. Also used for recompute-replays, whose
        token stream is prompt + generated-so-far. Cheap in steady state:
        an unchanged index (kv.index_version) skips the walk entirely, as
        does a request already matched to its cap."""
        if not (self.paged and self.prefix_share):
            return
        if req.match_version == self.kv.index_version:
            return
        req.match_version = self.kv.index_version
        total = min(req.l_in + len(req.output), self.kv.max_len)
        if req.shared_pages is not None and \
                len(req.shared_pages) >= total // self.kv.page_size:
            return                          # every full page already matched
        tokens = req.token_stream(total)
        pids = self.kv.pin_prefix(tokens)
        old = req.shared_pages or []
        if len(pids) <= len(old):
            self.kv.unpin(pids)
            return
        if old:
            self.kv.unpin(old)
        prev_start = req.prefill_pos
        start = min(len(pids) * self.kv.page_size, total - 1)
        req.shared_pages = pids
        req.prefill_pos = start
        self.shared_tokens_skipped += start - prev_start

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    # ---------------------------------------------------------------- counts
    def _expected_counts(self, T: int) -> np.ndarray:
        """Per-expert counts the planner should assume for a stage of T live
        tokens: the EMA of actual router counts rescaled to T (uniform
        expectation until the first stage reports back)."""
        m = self.cfg.moe
        total = float(T * m.top_k)
        if self._ema_counts is None or self._ema_counts.sum() <= 0:
            return np.full(m.num_experts, total / m.num_experts)
        return self._ema_counts * (total / self._ema_counts.sum())

    def _update_counts(self, counts_sum) -> Optional[np.ndarray]:
        """Fold one stage's summed-over-layers router counts into the EMA;
        returns the per-layer count vector for this stage's traffic model."""
        if counts_sum is None:
            return None
        c = np.asarray(counts_sum, np.float64)
        if self._moe_layers:
            c = c / self._moe_layers
        if c.sum() <= 0:
            return c
        if self._ema_counts is None:
            self._ema_counts = c
        else:
            d = self._count_ema_decay
            self._ema_counts = d * self._ema_counts + (1.0 - d) * c
        return c

    # ------------------------------------------------------------ preemption
    def _maybe_preempt(self, tnow: Optional[float] = None) -> None:
        """SVIII-C: reclaim capacity under pressure. Slot pressure (both
        layouts): a fresh request starving with zero free slots evicts a
        running request (migrate its KV to host, or drop it for later
        recomputation). Page pressure (paged): if the pool cannot cover the
        next stage's growth, evict lowest-priority requests page-granularly
        first — this is what makes pool oversubscription safe. With a clock,
        past-deadline requests are preferred victims (their work is dead
        either way — the sweep will expire them)."""
        from repro.serving import preemption as pre
        if self.preemption == "none":
            return
        if self.paged:
            self._preempt_for_pages(tnow)
        if self.kv.free_slots > 0:
            return
        q = self.scheduler.queue
        if not q or q[0].was_preempted:
            return                      # nothing starving / avoid thrash
        victim = pre.pick_victim(self.scheduler.running, tnow)
        if victim is None:
            return
        self._evict(victim)

    def _forced_evict(self, tnow: float) -> None:
        """Injected fault: evict a victim even though capacity is fine,
        exercising the recompute/migrate replay path and shared-prefix
        survival. Skipped when fewer than two requests are resident (same
        no-livelock rule as genuine page pressure)."""
        from repro.serving import preemption as pre
        cands = [r for r in (self.scheduler.running
                             + self.scheduler.prefilling) if r.slot >= 0]
        if len(cands) < 2:
            return
        victim = (pre.pick_victim_paged(cands, tnow) if self.paged
                  else pre.pick_victim(self.scheduler.running, tnow))
        if victim is None:
            return
        self._evict(victim)
        self.forced_evictions += 1

    def _evict(self, victim: Request) -> None:
        from repro.serving import preemption as pre
        self._slot_req.pop(victim.slot, None)
        if self.preemption == "migrate":
            pre.migrate_out(self.kv, victim)
        else:
            pre.recompute_out(self.kv, victim)
        self.scheduler.resubmit_preempted(victim)
        victim.queued_at = time.monotonic()
        # the replay can re-match whatever shared prefix pages survived the
        # eviction under their other owners (eviction may not change the
        # index, so force a fresh walk)
        victim.match_version = -1
        self._match_prefix(victim)
        self.preemptions += 1
        self._epoch += 1                # invalidates any speculative plan

    def _stage_page_need(self) -> int:
        """Worst-case fresh pages the NEXT stage's already-admitted work
        needs: one per decoding slot whose next token opens a page, the
        next chunk's growth per in-flight prefill, plus one COW page of
        slack per prefill (a shared capped last page copies on write)."""
        page = self.kv.page_size
        need = 0
        for r in self.scheduler.running:
            if r.slot >= 0 and int(self.kv.lens[r.slot]) % page == 0:
                need += 1
        budget = self.prefill_chunk_tokens or self.kv.max_len
        for r in self.scheduler.prefilling:
            if r.slot < 0:
                continue
            end = min(r.prefill_pos + budget, r.prefill_total)
            need += max(-(-end // page) - self.kv.slot_page_count(r.slot), 0)
            if self.prefix_share:
                need += 1
        return need

    def _lifetime_pages(self, req: Request) -> int:
        """Pages ``req`` needs by the time it finishes generating (its
        final decode write covers position l_in + max_new_tokens - 1),
        capped at max_len."""
        total = min(req.l_in + req.max_new_tokens, self.kv.max_len)
        return -(-total // self.kv.page_size)

    def _remaining_demand_pages(self) -> int:
        """Fresh pages the already-admitted work still needs over its whole
        REMAINING LIFETIME (prefill + every future decode token), plus COW
        slack per shared prefill. With preemption disabled this is what
        admission must reserve so ``ensure_len`` can never fail."""
        need = 0
        for r in self.scheduler.running + self.scheduler.prefilling:
            if r.slot < 0:
                continue
            need += max(self._lifetime_pages(r)
                        - self.kv.slot_page_count(r.slot), 0)
        if self.prefix_share:
            need += len(self.scheduler.prefilling)
        return need

    def _preempt_for_pages(self, tnow: Optional[float] = None) -> None:
        """Evict until the pool covers the next stage's growth ("alloc
        would fail" → page-granular eviction, ISSUE/paper SVIII-C). Shared
        pages survive eviction under their other owners, so evicting one
        branch of a shared prefix reclaims only its private tail. Never
        evicts the last resident request — a single context that outgrows
        the pool cannot be saved by eviction, and ensure_len's error is the
        honest outcome."""
        from repro.serving import preemption as pre
        while self.kv.free_pages < self._stage_page_need():
            cands = [r for r in (self.scheduler.running
                                 + self.scheduler.prefilling) if r.slot >= 0]
            if len(cands) <= 1:
                return
            victim = pre.pick_victim_paged(cands, tnow)
            if victim is None:
                return
            self._evict(victim)

    def _admit_restored(self, req, tnow: float) -> None:
        """Re-admit a migrated request: scatter its host-saved KV back into
        a fresh slot and resume decoding (no recompute)."""
        from repro.serving import preemption as pre
        slot = self._claim_slot(req)
        pre.restore_slot(self.kv, slot, req.saved_cache)
        req.saved_cache = None
        req.slot = slot
        self._slot_req[slot] = req
        self._tokens[slot] = req.output[-1]
        req.state = RequestState.DECODE

    def _claim_slot(self, req: Request) -> int:
        """A KV slot for ``req`` as it leaves the queue; its wait since it
        entered the queue is one ``engine.queue`` span."""
        slot = self.kv.allocate()
        if req.queued_at is not None:
            tracing.mark("engine.queue", req.queued_at, time.monotonic(),
                         rid=req.rid)
        return slot

    # ---------------------------------------------------------------- stages
    def _invoke(self, fn, *args):
        """Run a jitted stage step through the injector's transient-error
        schedule: each attempt may "fail" (a drawn step error), costing a
        retry plus virtual backoff; ``max_retries`` consecutive failures
        raise :class:`InjectedStepError` and the whole stage aborts. Safe
        because step functions are pure — a retried attempt reads the same
        cache state the failed one would have. Every jitted stage call
        passes through here, inside one ``engine.launch`` span."""
        with tracing.span("engine.launch") as sp:
            attempt = 0
            while self.injector is not None and self.injector.step_error():
                attempt += 1
                self.retries += 1
                self.fault_delay += self.injector.backoff(attempt)
                if attempt >= self.injector.max_retries:
                    raise InjectedStepError(
                        f"stage step failed {attempt} consecutive times "
                        f"(max_retries={self.injector.max_retries})")
            out = fn(*args)
        self._t_launched = sp.t1
        return out

    def _launch(self, fut: StageFuture, fn, args, outs) -> None:
        """Invoke one staged step call and keep its outputs: each name of
        ``outs`` is a :class:`StageFuture` field, except ``cache``, the KV
        cache the call returns."""
        for name, val in zip(outs, self._invoke(fn, *args), strict=True):
            if name == "cache":
                self.kv.cache = val
            else:
                setattr(fut, name, val)

    def _unique_page_bytes(self, slot_pages) -> int:
        """Streamed-KV bytes for a paged stage: UNIQUE pages across all the
        stage's readers (slot_pages = [(slot, live page count)]). A
        shared-prefix page read by N rows is resident once and counted
        once, so sharing shows up in the accounting exactly as it does in
        the pool."""
        seen = set()
        for s, n in slot_pages:
            seen.update(self.kv.block_tables[s, :n].tolist())
        seen.discard(0)
        return len(seen) * self.kv.page_size * self._kv_bytes_per_token

    def _staging(self, name: str, shape, dtype) -> np.ndarray:
        """A zeroed host staging buffer from the CURRENT double-buffer set
        (``dispatch_stage`` flips sets per stage). Reusing two alternating
        buffers keeps stage-input construction allocation-free in steady
        state, and guarantees the arrays stage N's transfer read are never
        overwritten while stage N+1's inputs are being built."""
        bufs = self._staging_bufs[self._staging_idx]
        buf = bufs.get(name)
        if buf is None or buf.shape != shape or buf.dtype != dtype:
            buf = np.zeros(shape, dtype)
            bufs[name] = buf
        else:
            buf.fill(0)
        return buf

    def _stage_decode(self, fut: StageFuture):
        """Inputs of a decoding-only stage (the dominant kind): host KV
        growth and input staging. Returns the step call for
        :meth:`_launch`, which leaves the next-token / router-count DEVICE
        arrays on ``fut`` without materializing them."""
        decision = fut.plan.decision
        k_cold = fut.plan.k_cold
        chain = fut.plan.chain
        if self.paged:
            page = self.kv.page_size
            slots = [r.slot for r in decision.decoding]
            proj = chain.proj_lens if chain is not None else None
            live_pages = []                # per-slot pages after this write
            for s in slots:
                # chained dispatch runs BEFORE the previous stage commits:
                # read the projected post-commit length, not kv.lens
                cur = int(self.kv.lens[s]) if proj is None else proj[s]
                target = min(cur + 1, self.kv.max_len)
                self.kv.ensure_len(s, target)
                if self.prefix_share:
                    # a decode write never targets a full shared page in
                    # steady state (sharing is full-page only), but the
                    # invariant "no scatter into refcount>1 pages" is
                    # enforced here, not assumed. The write position clamps
                    # to max_len-1 at capacity (the kernel clamps the same
                    # way), so a capped sequence's overwrite COWs/deindexes
                    # its last page instead of mutating an indexed one.
                    wpos = min(cur, self.kv.max_len - 1)
                    self.kv.ensure_writable(s, wpos, wpos + 1)
                live_pages.append(-(-target // page))
            fut.kv_bytes = self._unique_page_bytes(zip(slots, live_pages))
            nb = _bucket(len(slots), self.decode_bs_buckets)
            mp = _bucket(max(live_pages), self.pages_buckets)
            tokens = self._staging("d_tokens", (nb, 1), np.int32)
            lengths = self._staging("d_lengths", (nb,), np.int32)
            bt = self._staging("d_bt", (nb, mp), np.int32)
            for i, s in enumerate(slots):
                if proj is None:
                    tokens[i, 0] = self._tokens[s]
                    lengths[i] = self.kv.lens[s]   # pad: len 0 -> null page
                else:
                    lengths[i] = proj[s]
                bt[i] = self.kv.block_tables[s, :mp]
            fut.moe_caps = self._moe_caps(nb, k_cold)
            fn = self._paged_decode_fn(k_cold, *fut.moe_caps, nb, mp)
            # host staging buffers go to the jitted call as-is: pjit's
            # C++ arg path converts them an order of magnitude cheaper
            # than explicit jnp.asarray device_puts
            if chain is not None:
                fn, lead = chain.wrap(fn, self.params)
            else:
                lead = (self.params, tokens)
            return (fn, lead + (self.kv.cache, lengths, bt,
                                self._next_key()),
                    ("nxt", "cache", "counts"))
        # dense: runs over ALL slots — outputs of inactive slots are
        # discarded (and masked out of MoE routing), their cache is
        # overwritten on reuse, and their dead KV is streamed every stage.
        fut.kv_bytes = self._dense_kv_bytes_per_stage
        valid = self._staging("d_valid", (self.kv.max_slots,), bool)
        for r in decision.decoding:
            valid[r.slot] = True
        fut.moe_caps = self._moe_caps(self.kv.max_slots, k_cold)
        fn = self._decode_fn(k_cold, *fut.moe_caps)
        if chain is not None:
            fn, lead = chain.wrap(fn, self.params)
        else:
            toks = self._staging("d_toks", (self.kv.max_slots, 1), np.int32)
            toks[:, 0] = self._tokens
            lead = (self.params, toks)
        return (fn, lead + (valid, self.kv.cache, self._next_key()),
                ("nxt", "cache", "counts"))

    def _row_live(self, r: Request) -> bool:
        """Commit guard: may this in-flight row's result be applied to
        ``r``? False when the request finished abnormally / was evicted
        between dispatch and commit (async cancel, expiry, preemption) —
        its device work is discarded. A freed slot's garbage KV write is
        harmless: device program order lands it before any new owner's
        overwrite, and unwritten offsets are never read."""
        return (not r.done and r.slot >= 0
                and self._slot_req.get(r.slot) is r)

    def _commit_decode(self, fut: StageFuture, mat: Dict[str, Any],
                       tnow: float) -> None:
        """Commit half of a decoding-only stage: apply the materialized
        next tokens and advance ``kv.lens`` — the first point the stage
        becomes durable."""
        decision = fut.plan.decision
        nxt = mat["nxt"]
        emit = self.on_token is not None
        if self.paged:
            adv = []
            for i, r in enumerate(decision.decoding):
                if not self._row_live(r):
                    continue
                tok = int(nxt[i])
                self._tokens[r.slot] = tok
                r.record_token(tok, tnow)
                if emit:
                    fut.emitted.append((r.rid, tok))
                adv.append(r.slot)
            if adv:
                self.kv.lens[np.asarray(adv)] += 1
            return
        for r in decision.decoding:
            if not self._row_live(r):
                continue
            tok = int(nxt[r.slot])
            self._tokens[r.slot] = tok
            r.record_token(tok, tnow)
            if emit:
                fut.emitted.append((r.rid, tok))

    def _stage_mixed(self, fut: StageFuture):
        """Inputs of a unified mixed stage: first chunks claim their slots
        (admission — unwound by ``_abort_stage`` on an injected fault) and
        inputs stage for one jitted step over decode rows + chunk rows,
        returned for :meth:`_launch`; the final chunk of a prompt samples
        its first token at commit."""
        decision = fut.plan.decision
        k_cold = fut.plan.k_cold
        chunks = decision.chunks
        for c in chunks:                       # first chunk claims the slot
            if c.req.slot < 0:
                s = self._claim_slot(c.req)
                c.req.slot = s
                self._slot_req[s] = c.req
                if c.req.shared_pages:
                    # transfer the submit-time pin into the block table:
                    # the shared prefix is mapped at refcount+1, and this
                    # chunk starts at the first unshared position
                    self.kv.adopt_prefix(s, c.req.shared_pages)
                    c.req.shared_pages = None
        spec = any(c.draft is not None for c in chunks)
        nc_b = _bucket(len(chunks), self.seq_buckets)
        sc_b = _bucket(max(c.tokens for c in chunks), self.chunk_len_buckets)
        ctokens = self._staging("m_ctokens", (nc_b, sc_b), np.int32)
        starts = self._staging("m_starts", (nc_b,), np.int32)
        clens = self._staging("m_clens", (nc_b,), np.int32)
        for i, c in enumerate(chunks):
            if c.draft is not None:
                # verify span (PR 9): the last sampled — not yet written —
                # token followed by the draft; its KV lands at [start, end)
                # exactly like a prefill chunk's would
                seq = c.req.token_stream(c.start + 1)[c.start:] + \
                    list(c.draft)
            else:
                seq = c.req.token_stream(c.end)[c.start:]
            ctokens[i, :len(seq)] = seq
            starts[i] = c.start
            clens[i] = c.tokens
        chain = fut.plan.chain
        if self.paged:
            page = self.kv.page_size
            dslots = [r.slot for r in decision.decoding]
            proj = chain.proj_lens if chain is not None else None
            live_pages = [1]
            for s in dslots:
                # chained: projected post-commit length (see decode path)
                cur = int(self.kv.lens[s]) if proj is None else proj[s]
                target = min(cur + 1, self.kv.max_len)
                self.kv.ensure_len(s, target)
                if self.prefix_share:
                    # same no-scatter-into-shared-pages invariant as the
                    # decode-only stage (incl. the max_len-1 write clamp)
                    # — enforced on BOTH decode paths
                    wpos = min(cur, self.kv.max_len - 1)
                    self.kv.ensure_writable(s, wpos, wpos + 1)
                live_pages.append(-(-target // page))
            nb = _bucket(max(len(dslots), 1), self.decode_bs_buckets)
            mp = _bucket(max(live_pages), self.pages_buckets)
            dtokens = self._staging("m_dtokens", (nb, 1), np.int32)
            lengths = self._staging("m_lengths", (nb,), np.int32)
            bt = self._staging("m_bt", (nb, mp), np.int32)
            for i, s in enumerate(dslots):
                if proj is None:
                    dtokens[i, 0] = self._tokens[s]
                    lengths[i] = self.kv.lens[s]
                else:
                    lengths[i] = proj[s]
                bt[i] = self.kv.block_tables[s, :mp]
            cpages = []
            for c in chunks:
                self.kv.ensure_len(c.req.slot, c.end)
                if self.prefix_share:
                    # copy-on-write any shared page this chunk scatters
                    # into (the capped last page of a fully-shared prompt)
                    self.kv.ensure_writable(c.req.slot, c.start, c.end)
                cpages.append(-(-c.end // page))
            mpc = _bucket(max(cpages), self.pages_buckets)
            bt_c = self._staging("m_bt_c", (nc_b, mpc), np.int32)
            for i, c in enumerate(chunks):
                bt_c[i] = self.kv.block_tables[c.req.slot, :mpc]
            fut.kv_bytes = self._unique_page_bytes(
                list(zip(dslots, live_pages[1:]))
                + [(c.req.slot, n) for c, n in zip(chunks, cpages)])
            fut.moe_caps = self._moe_caps(nb + nc_b * sc_b, k_cold)
            fn = self._mixed_fn(k_cold, *fut.moe_caps, nc_b, sc_b,
                                nb, mp, mpc, spec)
            # a chained stage never carries verify spans (_build_chain
            # refuses them), so it never has the cn_all output
            if chain is not None:
                fn, lead = chain.wrap(fn, self.params)
            else:
                lead = (self.params, dtokens)
            args = lead + (lengths, bt, ctokens, starts, clens, bt_c,
                           self.kv.cache, self._next_key())
        else:
            cslots = self._staging("m_cslots", (nc_b,), np.int32)
            for i, c in enumerate(chunks):
                cslots[i] = c.req.slot
            valid = self._staging("m_valid", (self.kv.max_slots,), bool)
            for r in decision.decoding:
                valid[r.slot] = True
            # chunk rows gather + stream their slot's full cache row
            fut.kv_bytes = (self._dense_kv_bytes_per_stage
                            + len(chunks) * self.kv.max_len
                            * self._kv_bytes_per_token)
            fut.moe_caps = self._moe_caps(self.kv.max_slots + nc_b * sc_b,
                                          k_cold)
            fn = self._mixed_fn(k_cold, *fut.moe_caps, nc_b, sc_b,
                                spec=spec)
            if chain is not None:
                fn, lead = chain.wrap(fn, self.params)
            else:
                dtokens = self._staging("m_dtoks",
                                        (self.kv.max_slots, 1), np.int32)
                dtokens[:, 0] = self._tokens
                lead = (self.params, dtokens)
            args = lead + (valid, ctokens, cslots, starts, clens,
                           self.kv.cache, self._next_key())
        outs = (("nxt", "cn", "cn_all", "cache", "counts") if spec
                else ("nxt", "cn", "cache", "counts"))
        return fn, args, outs

    def _commit_mixed(self, fut: StageFuture, mat: Dict[str, Any],
                      tnow: float) -> None:
        """Commit half of a mixed stage: decode tokens + lens advance,
        chunk lens jump to each span's end (their pages were written on
        device), newly-full pages index for prefix sharing, each final
        chunk's sampled first token lands, and verify spans (PR 9) accept
        their longest agreeing draft prefix — rewinding the KV of any
        rejected tail."""
        decision = fut.plan.decision
        chunks = decision.chunks
        dn = mat["nxt"]
        cn = mat["cn"]
        emit = self.on_token is not None
        if self.paged:
            adv = []
            for i, r in enumerate(decision.decoding):
                if not self._row_live(r):
                    continue
                tok = int(dn[i])
                self._tokens[r.slot] = tok
                r.record_token(tok, tnow)
                if emit:
                    fut.emitted.append((r.rid, tok))
                adv.append(r.slot)
            if adv:
                self.kv.lens[np.asarray(adv)] += 1
            for c in chunks:
                if c.draft is not None or not self._row_live(c.req):
                    continue            # verify spans commit below
                self.kv.lens[c.req.slot] = c.end
                if self.prefix_share:
                    # index the newly-full pages under their token ids so
                    # later prompts (and post-eviction replays) can share
                    toks = c.req.token_stream(c.end)
                    self.kv.register_prefix(c.req.slot, toks)
        else:
            for r in decision.decoding:
                if not self._row_live(r):
                    continue
                tok = int(dn[r.slot])
                self._tokens[r.slot] = tok
                r.record_token(tok, tnow)
                if emit:
                    fut.emitted.append((r.rid, tok))
        for i, c in enumerate(chunks):
            if c.is_last and self._row_live(c.req):
                tok = int(cn[i])               # final chunk -> first token
                self._tokens[c.req.slot] = tok
                c.req.record_token(tok, tnow)
                if emit:
                    fut.emitted.append((c.req.rid, tok))
        if fut.cn_all is not None:
            self._commit_spec(fut, mat, tnow)

    def _commit_spec(self, fut: StageFuture, mat: Dict[str, Any],
                     tnow: float) -> None:
        """Commit the stage's verify spans (PR 9). For each span, position
        ``j`` of the verifier's per-position argmax (``cn_all``) is the
        greedy prediction for stream position ``start+j+1`` given inputs
        through ``start+j`` — identical, under greedy sampling, to what
        unspeculated decode would have sampled there. The span commits its
        longest agreeing draft prefix PLUS the verifier's own token at the
        first disagreement (the "bonus": a verify row always nets at least
        the one token plain decode would have produced). KV for the
        rejected tail is rolled back page-granularly (:meth:`KVManager.
        rewind`) or by resetting the dense device-side lengths — committed
        state is bit-identical to having never drafted."""
        decision = fut.plan.decision
        cn_all = mat["cn_all"]
        emit = self.on_token is not None
        dense_rw_slots: List[int] = []
        dense_rw_lens: List[int] = []
        for i, c in enumerate(decision.chunks):
            if c.draft is None:
                continue
            r = c.req
            if not self._row_live(r):
                continue                # died/evicted in flight: its pages
            row = cn_all[i]             # were freed wholesale already
            drafts = c.draft
            a = 0
            while a < len(drafts) and int(row[a]) == drafts[a]:
                a += 1
            self.spec_proposed += len(drafts)
            self.spec_accepted += a
            fut.spec_proposed += len(drafts)
            fut.spec_accepted += a
            cand = list(drafts[:a]) + [int(row[a])]
            m = 0
            for tok in cand:
                r.record_token(tok, tnow)
                if emit:
                    fut.emitted.append((r.rid, tok))
                m += 1
                if r.done:              # EOS / length inside the span:
                    break               # trailing accepts are discarded
            new_len = c.start + m       # last committed token stays
            if self.paged:              # unwritten, like plain decode
                self.kv.lens[r.slot] = c.end   # pages cover the span
                if r.done:
                    continue            # retire frees the slot wholesale
                if new_len < c.end:
                    self.kv.rewind(r.slot, new_len)
                    self.spec_rewinds += 1
            else:
                if r.done:
                    continue
                if new_len < c.end:
                    dense_rw_slots.append(r.slot)
                    dense_rw_lens.append(new_len)
                    self.spec_rewinds += 1
            self._tokens[r.slot] = cand[m - 1]
        if dense_rw_slots:
            self.kv.rewind_dense(dense_rw_slots, dense_rw_lens)

    def _stage_legacy_prefill(self, fut: StageFuture):
        """Inputs of the monolithic whole-prompt prefill (non-unified
        archs only), for a step into a fresh local cache; slots are claimed
        and the cache scattered at commit (pre-split behavior — nothing to
        unwind on an abort)."""
        assert not self.paged
        decision = fut.plan.decision
        # whole-prompt spans; a recompute-preempted replay covers prompt +
        # generated, capped at max_len by the scheduler — and max_len is
        # always a bucket, so no sequence outgrows its slab.
        seqs = [c.req.token_stream(c.end)
                for c in decision.chunks]
        n_b = _bucket(len(seqs), self.seq_buckets)
        max_l = max(len(sq) for sq in seqs)
        l_b = _bucket(max_l, self.prefill_len_buckets)
        tokens = self._staging("lp_tokens", (n_b, l_b), np.int32)
        true_len = self._staging("lp_true_len", (n_b,), np.int32)
        for i, sq in enumerate(seqs):
            tokens[i, :len(sq)] = sq
            true_len[i] = len(sq)
        return (self._legacy_prefill_fn(n_b, l_b),
                (self.params, tokens, true_len, self._next_key()),
                ("legacy_nxt", "legacy_cache"))

    def _commit_legacy_prefill(self, fut: StageFuture, mat: Dict[str, Any],
                               tnow: float) -> None:
        """Commit half of the legacy prefill: claim slots, scatter the
        local cache into them, record first tokens. Rows whose request
        died in flight are dropped before any slot is claimed."""
        nxt = mat["legacy_nxt"]
        fresh = [c.req for c in fut.plan.decision.chunks]
        live = [(i, r) for i, r in enumerate(fresh) if not r.done]
        if not live:
            fut.legacy_cache = None
            return
        slots = [self._claim_slot(r) for _, r in live]
        take = jnp.asarray([i for i, _ in live], dtype=jnp.int32)
        local = [jax.tree_util.tree_map(lambda a: a[:, take], seg)
                 for seg in fut.legacy_cache]
        self.kv.scatter(local, slots)
        fut.legacy_cache = None
        for (i, r), s in zip(live, slots):
            r.slot = s
            self._slot_req[s] = r
            tok = int(nxt[i])
            self._tokens[s] = tok
            r.record_token(tok, tnow)
            if self.on_token is not None:
                fut.emitted.append((r.rid, tok))

    def _abort_stage(self, decision: StageDecision) -> None:
        """Unwind a stage an injected fault interrupted. Nothing durable has
        advanced — ``kv.lens``, sampled tokens and ``commit_stage`` all
        happen after the jitted step — so the only state to restore is this
        stage's admissions: requests whose FIRST chunk claimed a slot (the
        explicit ``first`` flag — a continuing chunk keeps its slot and
        position) give the slot back and requeue at the head, and restored
        migrations requeue with their saved cache intact. Pages a continuing
        prefill's ``ensure_len`` already grew stay mapped (private, reused
        by the retry); COW copies keep their copied content. Requeued
        admissions re-match the prefix index so sharing survives the
        abort."""
        self.stage_aborts += 1
        requeue: List[Request] = []
        for c in decision.chunks:
            if not c.first:
                continue                 # continuing chunk: slot + pos kept
            r = c.req
            if r.slot >= 0:
                # the admission already claimed a slot (and adopted any
                # pinned prefix into it): free it — adopted pages decref,
                # surviving under other owners — and re-match from scratch
                self._slot_req.pop(r.slot, None)
                self.kv.free(r.slot)
                r.slot = -1
                r.shared_pages = None
                r.match_version = -1
                r.prefill_pos = 0
            # slot < 0 (legacy prefill allocates after the step): nothing
            # claimed yet — any queued-time pins stay valid and held
            r.state = RequestState.QUEUED
            r.prefill_target = None
            r.queued_at = time.monotonic()
            requeue.append(r)
        requeue.extend(decision.restored)
        for r in reversed(requeue):
            self.scheduler.queue.appendleft(r)
        for r in requeue:
            if r.saved_cache is None:
                self._match_prefix(r)

    def _run_audit(self) -> int:
        """Post-stage invariant audit (on under chaos, or explicitly via
        ``audit_stages=True``): checks the KV manager with EXACT pin
        expectations — queued requests' ``shared_pages`` are the only pin
        holders — and accumulates any violations. Returns this stage's
        violation count (0 = healthy)."""
        if not self.audit_stages:
            return 0
        pins: Optional[Dict[int, int]] = None
        if self.paged:
            pins = {}
            for r in self.scheduler.queue:
                for pid in (r.shared_pages or ()):
                    pins[pid] = pins.get(pid, 0) + 1
        errs = self.kv.audit(pins=pins)
        if errs:
            self.audit_violations += len(errs)
            self.audit_log.extend(
                f"stage {self._stage_idx}: {e}" for e in errs)
        return len(errs)

    # ------------------------------------------------ plan / dispatch / commit
    @tracing.traced("engine.plan.maintain")
    def _stage_maintenance(self, now: Optional[float] = None) -> float:
        """Pre-stage housekeeping, in the exact order of the pre-split
        engine: injected latency lands on the clock, the expiry sweep
        clears past-deadline work (releasing its capacity), preemption and
        the injected forced eviction reshape residency, and admissible
        queue heads re-match the prefix index. Returns the stage clock."""
        if self.injector is not None:
            self.fault_delay += self.injector.latency_spike()
        tnow = self._now(now)
        for r in self.scheduler.sweep_expired(tnow):
            self._finish_abnormal(r, "expired", tnow)
        self._maybe_preempt(tnow)
        if (self.injector is not None and self.preemption != "none"
                and self.injector.forced_eviction()):
            self._forced_evict(tnow)
        if self.paged and self.prefix_share:
            # refresh admissible queue heads against the CURRENT index —
            # requests submitted together find nothing at submit time; by
            # their admission stage the donor's prefix pages are resident
            for r in list(self.scheduler.queue
                          )[:self.scheduler.max_prefill_seqs]:
                if r.saved_cache is None and not r.done:
                    self._match_prefix(r)
        return tnow

    @tracing.traced("engine.plan.admit")
    def _page_admission_cap(self) -> int:
        """Paged admission backpressure: walk the queue in admission order,
        accumulating each candidate's demand minus the prefix pages it
        already shares (sharing directly raises the admitted batch), and
        cap this stage's admissions at the prefix that still fits. Without
        preemption the demand is the WHOLE LIFETIME (prompt + every future
        decode token) of admitted and candidate work, so ensure_len can
        never fail; with preemption enabled, admission is aggressive —
        only the next stage's growth plus the candidate's first chunk —
        and page-granular eviction reclaims capacity when generation
        outruns the pool (that is the oversubscription contract)."""
        page = self.kv.page_size
        conservative = self.preemption == "none"
        budget = self.prefill_chunk_tokens or self.kv.max_len
        need = (self._remaining_demand_pages() if conservative
                else self._stage_page_need())
        admit = 0
        for r in list(self.scheduler.queue
                      )[:self.scheduler.max_prefill_seqs]:
            shared = len(r.shared_pages or ())
            if conservative:
                d = max(self._lifetime_pages(r) - shared, 0)
            else:
                # the candidate's first chunk: starts at its first
                # unshared position, ends a budget later
                total = min(r.l_in + len(r.output), self.kv.max_len)
                end = min(r.prefill_pos + budget, total)
                d = max(-(-end // page) - shared, 0)
            need += d + (1 if shared and self.prefix_share else 0)
            if self.kv.free_pages < need:
                break
            admit += 1
        return admit

    @tracing.traced("engine.plan.duplex")
    def _finish_plan(self, decision: StageDecision,
                     snap: Tuple[int, int, int, int], tnow: float,
                     speculative: bool = False) -> StagePlan:
        """Wrap a scheduler decision into a :class:`StagePlan`: pick
        ``k_cold`` from the router-count EMA (for a speculative plan the
        EMA is one stage staler — the in-flight stage's counts fold in at
        its deferred commit; that changes only the execution-path choice,
        never the tokens) and run the Op/B dispatch model."""
        mix = decision.mix()
        k_cold = 0
        if self.use_duplex and mix.num_tokens > 0:
            # planner input: the EMA of actual previous-stage router counts
            # rescaled to this stage's token count (one-stage-stale
            # statistics); the jitted step re-ranks experts from *actual*
            # counts — only the width is static.
            k_cold = self.planner.k_cold_static(
                self._expected_counts(mix.num_tokens))
        splan = (core_plan_stage(self.cfg, mix, kv_quant=self.kv.kv_quant)
                 if mix.num_tokens else None)
        return StagePlan(decision=decision, k_cold=k_cold, splan=splan,
                         snap=snap, tnow=tnow,
                         speculative=speculative, epoch=self._epoch)

    @tracing.traced("engine.plan.draft")
    def _build_drafts(self) -> Optional[Dict[int, Tuple[int, List[int]]]]:
        """PR 9: host-side n-gram drafting for the next stage. For every
        decode-eligible row, ask the :class:`NgramDrafter` for up to
        ``spec_k`` continuation tokens from the request's OWN stream
        (prompt lookup — no second model), capped by the remaining token
        budget (a verify span commits at most ``k+1`` tokens), the KV
        capacity, and — under paged preemption — the page-pool slack left
        after the already-admitted work's worst-case growth (drafting must
        never push ``ensure_len`` into a pool the preemption planner
        thinks is fine). Returns ``{rid: (start, draft_tokens)}`` for the
        scheduler to turn into verify :class:`ChunkSpan`s, or None when
        nothing drafted."""
        drafts: Dict[int, Tuple[int, List[int]]] = {}
        slack = (self.kv.free_pages - self._stage_page_need()
                 if self.paged else 0)
        for r in self.scheduler.running:
            if r.done or r.slot < 0 or r.state != RequestState.DECODE:
                continue
            if self.paged:
                start = int(self.kv.lens[r.slot])
            else:
                start = r.l_in + len(r.output) - 1
            k = min(self.drafter.k,
                    r.max_new_tokens - len(r.output) - 1,
                    self.kv.max_len - start - 1)
            if k < 1:
                continue
            toks = self.drafter.draft(r.token_stream())[:k]
            if not toks:
                continue
            if self.paged:
                base = self.kv.page_need(r.slot, start + 1)
                while toks:
                    extra = self.kv.page_need(
                        r.slot, start + len(toks) + 1) - base
                    if extra <= slack:
                        slack -= extra
                        break
                    toks = toks[:-1]
                if not toks:
                    continue
            drafts[r.rid] = (start, toks)
        return drafts or None

    @tracing.traced("engine.plan")
    def plan_stage(self, now: Optional[float] = None, *,
                   maintain: bool = True,
                   snap: Optional[Tuple[int, int, int, int]] = None
                   ) -> Optional[StagePlan]:
        """Form the next stage from REAL state: stage maintenance
        (``maintain=False`` when the caller already ran it this turn —
        the re-plan after an invalidated speculative plan must not draw
        the chaos schedule twice), the paged admission cap, the
        scheduler's span/admission walk, and the Op/B execution plan.
        Pure host work, no device sync. Returns None when no stage can be
        formed."""
        if snap is None:
            snap = (self.shed, self.expired, self.cancelled, self.retries)
        tnow = self._stage_maintenance(now) if maintain else self._now(now)
        free = self.kv.free_slots
        if self.paged:
            free = min(free, self._page_admission_cap())
        drafts = self._build_drafts() if self.drafter is not None else None
        with tracing.span("engine.plan.schedule"):
            decision = self.scheduler.next_stage(free, drafts=drafts)
        if decision is None:
            return None
        return self._finish_plan(decision, snap, tnow)

    @tracing.traced("engine.dispatch")
    def dispatch_stage(self, plan: StagePlan) -> StageFuture:
        """Enqueue a planned stage on the device WITHOUT waiting for it:
        speculative plans activate their admissions first (the plan never
        touched the scheduler), first chunks claim slots, inputs stage
        into the flipped double buffer, and the jitted step call returns
        immediately with device-array futures (JAX async dispatch). An
        injected chaos fault raises :class:`InjectedFault` out of here —
        callers unwind via ``_abort_stage``, exactly as the pre-split
        engine did around its stage body."""
        if plan.speculative:
            self.scheduler.activate(plan.decision)
        self._staging_idx ^= 1
        fut = StageFuture(plan=plan)
        decision = plan.decision
        if decision.chunks and self._unified:
            calls = (self._stage_mixed,)
        else:                                # legacy: non-unified archs only
            calls = (((self._stage_decode,) if decision.decoding else ())
                     + ((self._stage_legacy_prefill,) if decision.chunks
                        else ()))
        for stage_inputs in calls:
            with tracing.span("engine.dispatch.inputs"):
                call = stage_inputs(fut)
            self._launch(fut, *call)
        if plan.chain is not None:
            # chained dispatch: enqueued BEFORE the in-flight stage's sync
            # point, while the device is still executing it — the idle
            # window between the two stages is structurally zero
            self.gap_stages += 1
            self.chained_stages += 1
            self._t_sync_done = None
        elif self._t_sync_done is not None:
            # host stage gap: the device-idle window between the previous
            # stage's materialization and this enqueue — what the async
            # loop exists to shrink
            self.host_gap_s += max(self._t_launched - self._t_sync_done, 0.0)
            self.gap_stages += 1
            self._t_sync_done = None
        return fut

    def _materialize(self, fut: StageFuture) -> Dict[str, Any]:
        """Block on the stage's device token arrays — the pipeline's ONLY
        device sync point. The async loops call this OUTSIDE the lock so
        client submits/cancels and fleet polls never wait behind device
        compute."""
        mat: Dict[str, Any] = {}
        with tracing.span("engine.sync") as sp:
            if fut.nxt is not None:
                mat["nxt"] = np.asarray(fut.nxt)
            if fut.cn is not None:
                mat["cn"] = np.asarray(fut.cn)
            if fut.cn_all is not None:
                mat["cn_all"] = np.asarray(fut.cn_all)
            if fut.legacy_nxt is not None:
                mat["legacy_nxt"] = np.asarray(fut.legacy_nxt)
        self._t_sync_done = sp.t1
        return mat

    @tracing.traced("engine.commit")
    def _commit_critical(self, fut: StageFuture,
                         mat: Dict[str, Any]) -> None:
        """The durable half of a commit — everything the NEXT stage's
        dispatch depends on: sampled tokens, ``kv.lens`` advances, prefix
        index registration, migrated-back restores, retirement of finished
        slots, and the scheduler's position/promotion bookkeeping. Runs
        under the lock; accounting nothing downstream reads is deferred
        (:meth:`_commit_deferred`) past the next dispatch in the async
        loops. Also freezes this stage's robustness-counter deltas so the
        deferred report cannot absorb the next stage's window."""
        plan = fut.plan
        decision = plan.decision
        tnow = plan.tnow
        if decision.chunks and self._unified:
            self._commit_mixed(fut, mat, tnow)
        else:
            if decision.decoding:
                self._commit_decode(fut, mat, tnow)
            if decision.chunks:              # non-unified archs only
                self._commit_legacy_prefill(fut, mat, tnow)
        # migrated-back requests restore AFTER the stage ran: the dense
        # decode half sweeps every slot and would advance a just-restored
        # slot's length past its real context.
        for r in decision.restored:
            if not r.done and r.saved_cache is not None:
                self._admit_restored(r, tnow)
        # ---- retire
        for r in ([c.req for c in decision.chunks] + decision.decoding
                  + decision.restored):
            if r.done and r.slot >= 0:
                self.kv.free(r.slot)
                self._slot_req.pop(r.slot, None)
        self.scheduler.commit_stage(decision)
        fut.deltas = (self.shed - plan.snap[0],
                      self.expired - plan.snap[1],
                      self.cancelled - plan.snap[2],
                      self.retries - plan.snap[3])

    @tracing.traced("engine.account")
    def _commit_deferred(self, fut: StageFuture) -> StageReport:
        """The accounting half of a commit: router-count EMA, the MoE
        streamed-bytes / padded-vs-live FLOP traffic model, the
        :class:`StageReport`, the post-stage audit and the peak-occupancy
        counter. Nothing the next stage's plan or dispatch reads — the
        async loops run it AFTER the next dispatch is already on device.
        (The audit stays safe there: pages grown ahead of ``kv.lens`` by
        an in-flight dispatch satisfy ``lens <= pages * page_size``.)"""
        plan = fut.plan
        decision = plan.decision
        k_cold = plan.k_cold
        if self.on_token is not None and fut.emitted:
            # streaming callbacks (PR 9 satellite): fired HERE, off the
            # deferred path — a slow consumer can never stall the critical
            # commit section or the next stage's dispatch
            for rid, tok in fut.emitted:
                self.on_token(rid, tok)
            fut.emitted = []
        counts_layer = self._update_counts(fut.counts)
        chunk_tokens = sum(c.tokens for c in decision.chunks)
        live_moe = len(decision.decoding) + chunk_tokens
        moe_bytes = moe_flops_live = moe_flops_padded = 0
        if (self.use_duplex and live_moe and self._moe_layers
                and fut.moe_caps is not None
                and (k_cold > 0 or self.moe_ragged)):
            from repro.core.duplex_moe import moe_traffic_model
            m = self.cfg.moe
            if counts_layer is not None and counts_layer.sum() > 0:
                dcounts = np.round(counts_layer).astype(np.int64)
            else:
                dcounts = np.round(
                    self._expected_counts(live_moe)).astype(np.int64)
            ch, cc, cb = fut.moe_caps
            stats = moe_traffic_model(dcounts, k_cold=k_cold, c_hot=ch,
                                      c_cold=cc, d_model=self.cfg.d_model,
                                      d_ff=m.d_ff_expert, c_block=cb,
                                      itemsize=self._param_itemsize,
                                      mats=self._moe_mats)
            L = self._moe_layers
            which = "ragged" if self.moe_ragged else "padded"
            moe_bytes = stats[f"{which}_bytes"] * L
            moe_flops_live = stats["ragged_flops"] * L
            moe_flops_padded = stats["padded_flops"] * L

        report = StageReport(
            stage_index=self._stage_idx, is_mixed=decision.is_mixed,
            num_decode=len(decision.decoding),
            num_prefill=len(decision.chunks), k_cold=k_cold,
            bandwidth_flop_fraction=(plan.splan.bandwidth_fraction()
                                     if plan.splan else 0.0),
            kv_bytes_streamed=int(fut.kv_bytes),
            moe_bytes_streamed=int(moe_bytes),
            moe_flops_live=int(moe_flops_live),
            moe_flops_padded=int(moe_flops_padded),
            chunk_tokens=int(chunk_tokens),
            stage_tokens=int(live_moe),
            shared_kv_pages=self.kv.shared_pages,
            shed=fut.deltas[0], expired=fut.deltas[1],
            cancelled=fut.deltas[2], retries=fut.deltas[3],
            spec_proposed=fut.spec_proposed,
            spec_accepted=fut.spec_accepted,
            audit_violations=self._run_audit())
        self.reports.append(report)
        self.peak_active = max(self.peak_active,
                               len(decision.decoding) + len(decision.chunks)
                               + len(decision.restored))
        self._stage_idx += 1
        return report

    def _abort_report(self, plan: StagePlan) -> StageReport:
        """Report a stage an injected fault unwound (``_abort_stage`` has
        already run): admissions are back at the queue head and nothing
        advanced."""
        decision = plan.decision
        report = StageReport(
            stage_index=self._stage_idx, is_mixed=decision.is_mixed,
            num_decode=len(decision.decoding),
            num_prefill=len(decision.chunks), k_cold=plan.k_cold,
            bandwidth_flop_fraction=0.0, aborted=True,
            shed=self.shed - plan.snap[0],
            expired=self.expired - plan.snap[1],
            cancelled=self.cancelled - plan.snap[2],
            retries=self.retries - plan.snap[3],
            audit_violations=self._run_audit())
        self.reports.append(report)
        self._stage_idx += 1
        return report

    def commit_stage(self, fut: StageFuture) -> StageReport:
        """Materialize and fully commit an in-flight stage — the
        synchronous composition ``step()`` uses. The async loops call the
        halves directly so the accounting half can defer past the next
        stage's dispatch."""
        mat = self._materialize(fut)
        self._commit_critical(fut, mat)
        return self._commit_deferred(fut)

    def step(self, now: Optional[float] = None) -> Optional[StageReport]:
        """Run one continuous-batching stage synchronously: plan →
        dispatch → commit, with semantics and chaos draw order identical
        to the pre-split engine. Returns None when idle. ``now`` overrides
        the wall clock (virtual-time benchmarks drive the deadline
        machinery deterministically through it).

        Stage order: injected latency lands on the clock; the expiry sweep
        clears past-deadline work (releasing its capacity); preemption and
        the injected forced eviction reshape residency; then admission and
        the stage body run. An injected fault inside the stage body
        unwinds via ``_abort_stage`` — this stage's admissions return to
        the queue head, nothing advanced (durable state only moves in the
        commit) — and the stage reports ``aborted=True``. The lock is held
        across the whole stage, so concurrent submits/cancels/polls land
        between stages."""
        with self._lock, tracing.span("engine.step") as sp:
            plan = self.plan_stage(now)
            if plan is None:
                return None
            try:
                fut = self.dispatch_stage(plan)
            except InjectedFault:
                self._abort_stage(plan.decision)
                rep = self._abort_report(plan)
            else:
                rep = self.commit_stage(fut)
            sp.stage = rep.stage_index
            return rep

    # ------------------------------------------------- speculation (async)
    @tracing.traced("engine.plan")
    def _plan_speculative(self, cur: StagePlan) -> Optional[StagePlan]:
        """Plan stage N+1 from the PROJECTED post-commit state of the
        in-flight stage N, touching no scheduler or request state.
        Predictable commit outcomes project exactly: chunk positions
        advance to their span ends, length-limit finishes retire and free
        their slots, final chunks and migrated-back restores join the
        decode set. Unpredictable ones (an EOS finish) are assumed
        "continues" — ``_validate_speculative`` re-checks against real
        post-commit state at dispatch time, so a wrong guess costs one
        re-plan, never a wrong token. Under-projection is SAFE (planned
        work ⊆ allowed work), so the projection leans conservative."""
        d = cur.decision
        if d.chunks and not self._unified:
            return None          # legacy prefill claims slots at commit
        if any(c.draft is not None for c in d.chunks):
            # PR 9: the in-flight stage verifies drafts — how many it
            # accepts (and how far each row's KV rewinds) is unknowable
            # before materialization, so any projection past it is a
            # guaranteed invalidation. A pending rewind IS a spec-miss:
            # skip the projection and re-plan (with fresh drafts from the
            # committed stream) after the commit lands.
            self.spec_misses += 1
            self._reject_spec("rewind")
            return None
        pos: Dict[int, int] = {}
        done_rids = set()
        finished_prefill = set()     # in-flight final chunks: promote at
        promoted: List[Request] = []  # commit, leave the prefilling set
        extra_prefilling: List[Request] = []
        freed = 0
        for c in d.chunks:
            r = c.req
            if r.done:
                continue         # died after dispatch; commit drops the row
            if c.is_last:
                finished_prefill.add(r.rid)
                # the final chunk samples the request's first token: a
                # length-limit finish is certain, an EOS finish is not
                if r.max_new_tokens <= 1:
                    done_rids.add(r.rid)
                    freed += 1
                else:
                    promoted.append(r)
            else:
                pos[r.rid] = c.end
                if r not in self.scheduler.prefilling:
                    extra_prefilling.append(r)   # in-flight admission
        for r in d.decoding:
            if not r.done and len(r.output) + 1 >= r.max_new_tokens:
                done_rids.add(r.rid)             # certain length finish
                freed += 1
        restored_live = [r for r in d.restored
                         if not r.done and r.saved_cache is not None]
        # projected decode set, in the exact order commit_stage builds it:
        # surviving decoders, then final-chunk promotions, then restores
        running_proj = [r for r in self.scheduler.running
                        if r.state == RequestState.DECODE
                        and r.rid not in done_rids]
        running_proj += promoted
        running_proj += restored_live
        if self.drafter is not None and running_proj:
            # PR 9: the next stage would draft for these decode rows, but
            # drafts n-gram-match against tokens the in-flight stage has
            # not committed yet — a projected plan could only offer the
            # undrafted (slower) stage. Fall back to plan-after-commit so
            # every decode stage gets fresh drafts; pure-prefill stages
            # still project and chain as before.
            self.spec_misses += 1
            self._reject_spec("draft")
            return None
        prefilling_proj = ([r for r in self.scheduler.prefilling
                            if not r.done
                            and r.rid not in finished_prefill]
                           + extra_prefilling)
        queue_proj = [r for r in self.scheduler.queue if not r.done]
        # slots: predicted finishes free theirs at retire; restores claim
        # theirs at commit (in-flight first chunks already claimed at
        # dispatch, so kv.free_slots reflects them)
        free = max(self.kv.free_slots + freed - len(restored_live), 0)
        if self.paged:
            # current-state page cap — in-flight growth makes this an
            # approximation either way; validation re-checks the real cap
            free = min(free, self._page_admission_cap())
        with tracing.span("engine.plan.schedule"):
            decision = self.scheduler.plan_stage(
                free, prefilling=prefilling_proj, running=running_proj,
                queue=queue_proj, pos=pos)
        if decision is None:
            return None
        snap = (self.shed, self.expired, self.cancelled, self.retries)
        return self._finish_plan(decision, snap, self._now(None),
                                 speculative=True)

    def _build_chain(self, spec: StagePlan, fut: StageFuture
                     ) -> Optional[ChainInfo]:
        """Decide whether speculative stage N+1 may dispatch BEFORE stage
        N materializes, and build its device-side token chaining. Eligible
        when every decode input token is either host-known now or a row of
        N's device output (the gather in :func:`_select_tokens`), and when
        everything the dispatch claims — slots for admissions, pages for
        KV growth — fits the CURRENT pool: a chained stage must never
        depend on N's retires landing first, because they haven't.
        Ineligible plans aren't misses; they fall back to the
        validate-after-commit path (one sync gap, no re-plan)."""
        d_prev = fut.plan.decision
        d = spec.decision
        if d.restored or d_prev.restored:
            return None        # restores scatter saved KV into the cache
        if any(c.draft is not None for c in d_prev.chunks) \
                or any(c.draft is not None for c in d.chunks):
            # verify spans (PR 9): accept length / KV rewind are decided
            # at commit, so neither side of a chain may carry them
            return None
        if fut.nxt is None:    # at commit — a chained reader would race it
            return None
        n_first = sum(1 for c in d.chunks if c.first)
        if n_first:
            if n_first > self.kv.free_slots:
                return None
            if self.paged and n_first > self._page_admission_cap():
                return None
        # paged nxt rows follow N's decoding order; dense nxt is by slot
        if self.paged:
            idx_nxt = {r.rid: i for i, r in enumerate(d_prev.decoding)}
        else:
            idx_nxt = {r.rid: r.slot for r in d_prev.decoding}
        idx_cn = {c.req.rid: i for i, c in enumerate(d_prev.chunks)
                  if c.is_last}
        n = _bucket(max(len(d.decoding), 1) if d.chunks
                    else len(d.decoding),
                    self.decode_bs_buckets) if self.paged \
            else self.kv.max_slots
        src_n = np.full(n, -1, np.int32)
        src_c = np.full(n, -1, np.int32)
        fb = np.zeros(n, np.int32)
        proj: Dict[int, int] = {}
        page_need = 0
        for i, r in enumerate(d.decoding):
            if r.done or r.slot < 0 or self._slot_req.get(r.slot) is not r:
                # the projected row lost its slot since N dispatched (a
                # forced eviction or expiry at this turn's maintenance) —
                # the validate path will re-plan; chaining would read and
                # write through a dead or re-owned slot
                return None
            j = i if self.paged else r.slot
            if r.rid in idx_nxt:
                src_n[j] = idx_nxt[r.rid]
                plen = 1       # kv.lens advances by one at commit N
            elif r.rid in idx_cn:
                # promoted final chunk: commit N jumps its len to the
                # span end, and its first token is N's cn row
                src_c[j] = idx_cn[r.rid]
                plen = None
            else:
                fb[j] = int(self._tokens[r.slot])
                plen = 0
            if self.paged:
                plen = (d_prev.chunks[idx_cn[r.rid]].end if plen is None
                        else int(self.kv.lens[r.slot]) + plen)
                proj[r.slot] = plen
                page_need += self.kv.page_need(
                    r.slot, min(plen + 1, self.kv.max_len))
        if self.paged:
            for c in d.chunks:
                if c.req.slot >= 0:
                    page_need += self.kv.page_need(c.req.slot, c.end)
                else:
                    # fresh admission: upper bound — prefix adoption at
                    # dispatch can only reduce the fresh-page need
                    page_need += -(-c.end // self.kv.page_size)
            if page_need > self.kv.free_pages:
                return None
        prev_cn = fut.cn if fut.cn is not None \
            else np.zeros(1, np.int32)
        return ChainInfo(src_nxt=src_n, src_cn=src_c, fallback=fb,
                         prev_nxt=fut.nxt, prev_cn=prev_cn,
                         proj_lens=proj)

    def _validate_speculative(self, spec: StagePlan, tnow: float) -> bool:
        """Decide whether a speculative plan may dispatch against REAL
        post-commit state (the fallback for plans that could not chain
        pre-sync). Checks SAFETY, not maximality: a plan that under-admits
        merely idles capacity for one stage, while a stale span or slot
        would corrupt state. Any epoch bump — a submit, cancel, eviction
        or expiry since the plan was formed — rejects wholesale. The
        turn's stage maintenance has already run by the time this is
        called."""
        if spec.epoch != self._epoch:
            return self._reject_spec("epoch")
        spec.tnow = tnow
        d = spec.decision
        for c in d.chunks:
            r = c.req
            if r.done:
                return self._reject_spec("chunk-done")
            if c.first:
                if r.saved_cache is not None \
                        or r not in self.scheduler.queue:
                    return self._reject_spec("admission-gone")
                total = len(r.prompt) + len(r.output)
                if self.scheduler.max_prefill_target is not None:
                    total = min(total, self.scheduler.max_prefill_target)
                start = min(r.prefill_pos, total - 1) if total > 0 else 0
                # a late prefix-index hit moves the start — re-plan to
                # pick up the longer share instead of a stale span
                if start != c.start or c.target != total:
                    return self._reject_spec("admission-span")
            elif r not in self.scheduler.prefilling \
                    or r.prefill_pos != c.start:
                return self._reject_spec("chunk-position")
        for r in d.decoding:
            if (r.done or r.slot < 0
                    or r.state != RequestState.DECODE
                    or self._slot_req.get(r.slot) is not r):
                return self._reject_spec("decode-row")
        for r in d.restored:
            if (r.done or r.saved_cache is None
                    or r not in self.scheduler.queue):
                return self._reject_spec("restore-gone")
        admissions = sum(1 for c in d.chunks if c.first) + len(d.restored)
        if admissions:
            if admissions > self.kv.free_slots:
                return self._reject_spec("free-slots")
            if self.paged and admissions > self._page_admission_cap():
                return self._reject_spec("page-cap")
        return True

    def _reject_spec(self, reason: str) -> bool:
        """Count why a speculative plan was invalidated (observability:
        ``stats()['spec_miss_reasons']``) and reject it."""
        self.spec_miss_reasons[reason] = \
            self.spec_miss_reasons.get(reason, 0) + 1
        return False

    @tracing.traced("engine.turn")
    def _pipeline_turn(self, fut: StageFuture,
                       now: Optional[float] = None, dispatch: bool = True
                       ) -> Tuple[Optional[StageFuture],
                                  Optional[StageReport], bool]:
        """One turn of the pipelined loop around an in-flight stage N.
        Fast path: run the turn's maintenance, speculatively plan N+1 and
        — when its inputs chain on N's device futures
        (:meth:`_build_chain`) — dispatch it BEFORE materializing N, so
        the device-idle window is structurally zero: N+1 is already
        enqueued when N finishes. Then materialize N (outside the lock —
        the only device wait), commit its durable half, and for plans
        that could not chain, validate-or-replan and dispatch behind the
        commit. Stage N's deferred accounting always runs behind the new
        dispatch. Returns ``(in-flight future, stage N's report, whether
        a new stage was formed)``."""
        new_fut = None
        aborted = None
        spec = None
        chained = False
        tnow = 0.0
        with self._lock:
            if dispatch:
                # the same per-stage maintenance draws the sync path makes
                # (spikes, expiry, preemption, prefix rematch) — once per
                # turn, before planning, so the chained and fallback paths
                # see identical schedules
                tnow = self._stage_maintenance(now)
                spec = self._plan_speculative(fut.plan)
                if spec is not None:
                    spec.tnow = tnow
                    chain = self._build_chain(spec, fut)
                    if chain is not None:
                        spec.chain = chain
                        try:
                            new_fut = self.dispatch_stage(spec)
                            chained = True
                            self.spec_hits += 1
                        except InjectedFault:
                            self._abort_stage(spec.decision)
                            aborted = spec
                        spec = None
        mat = self._materialize(fut)
        with self._lock:
            self._commit_critical(fut, mat)
            formed = chained
            if dispatch and not chained and aborted is None:
                snapnow = (self.shed, self.expired, self.cancelled,
                           self.retries)
                if spec is not None and self._validate_speculative(spec,
                                                                   tnow):
                    spec.snap = snapnow
                    self.spec_hits += 1
                    nxt_plan = spec
                elif spec is not None:
                    # the commit contradicted the projection (EOS finish,
                    # cancel, eviction, expiry, a moved prefix start):
                    # re-plan from real state — maintenance already ran
                    self.spec_misses += 1
                    nxt_plan = self.plan_stage(now, maintain=False,
                                               snap=snapnow)
                else:
                    # maintenance already ran at the top of the turn
                    nxt_plan = self.plan_stage(now, maintain=False,
                                               snap=snapnow)
                if nxt_plan is not None:
                    formed = True
                    try:
                        new_fut = self.dispatch_stage(nxt_plan)
                    except InjectedFault:
                        self._abort_stage(nxt_plan.decision)
                        aborted = nxt_plan
            report = self._commit_deferred(fut)
            if aborted is not None:
                # report order: stage N's deferred report first, then the
                # aborted stage N+1
                self._abort_report(aborted)
        return new_fut, report, formed

    def run_async(self, requests: List[Request], *,
                  max_stages: int = 10_000, stall_stages: int = 500,
                  max_wall_s: Optional[float] = None) -> List[Request]:
        """Drive submitted requests to drain through the PIPELINED loop:
        while stage N executes on device, the host commits N−1's deferred
        accounting and plans/dispatches N+1 from projected state. Token
        streams are identical to :meth:`run` under greedy sampling — the
        engine's cross-layout parity tests prove batch composition never
        changes sampled tokens, and speculation only ever changes
        composition, never content. Watchdog contract matches ``run()``:
        a descriptive :class:`EngineStalledError` instead of a silent
        spin, with the in-flight stage noted."""
        t_start = time.monotonic()
        for r in requests:
            try:
                self.submit(r)
            except AdmissionRejected:
                r.finish("rejected", self._now())
        stages = 0
        idle = 0
        last = self._progress()
        fut: Optional[StageFuture] = None
        while True:
            if (max_wall_s is not None
                    and time.monotonic() - t_start > max_wall_s):
                raise EngineStalledError(self._stall_msg(
                    f"wall budget {max_wall_s}s exhausted",
                    inflight=fut is not None))
            if fut is None:
                with self._lock:
                    if not self.scheduler.has_work:
                        break
                    if stages >= max_stages:
                        raise EngineStalledError(self._stall_msg(
                            f"max_stages={max_stages} exhausted with work "
                            f"pending"))
                    plan = self.plan_stage()
                    if plan is None:
                        if not self.scheduler.has_work:
                            break       # drained by the expiry sweep
                        raise EngineStalledError(self._stall_msg(
                            "no stage could be formed (capacity livelock "
                            "— queued work cannot be admitted and nothing "
                            "is running)"))
                    try:
                        fut = self.dispatch_stage(plan)
                    except InjectedFault:
                        self._abort_stage(plan.decision)
                        self._abort_report(plan)
                    stages += 1
                continue
            fut, _, formed = self._pipeline_turn(
                fut, dispatch=stages < max_stages)
            stages += int(formed)
            prog = self._progress()
            if prog > last:
                last, idle = prog, 0
            else:
                idle += 1
                if idle >= stall_stages:
                    raise EngineStalledError(self._stall_msg(
                        f"no progress across {idle} consecutive stages",
                        inflight=fut is not None))
        return requests

    def step_async(self, now: Optional[float] = None
                   ) -> Optional[StageReport]:
        """One pipelined tick for an external driver (the fleet): commit
        the previous tick's in-flight stage if one exists, dispatch the
        next and leave it in flight. Returns the COMMITTED stage's report
        — one tick stale relative to ``step()`` — or None when priming or
        idle. A replica killed mid-flight simply drops ``_inflight``:
        nothing durable advanced, which is the exactly-once failover
        contract. ``scheduler.has_work`` stays true while a stage is in
        flight (its requests sit in running/prefilling until commit), so
        drain detection needs no extra machinery."""
        fut, self._inflight = self._inflight, None
        if fut is not None:
            self._inflight, report, _ = self._pipeline_turn(fut, now)
            return report
        with self._lock:
            plan = self.plan_stage(now)
            if plan is None:
                return None
            try:
                self._inflight = self.dispatch_stage(plan)
            except InjectedFault:
                self._abort_stage(plan.decision)
                return self._abort_report(plan)
        return None

    # ------------------------------------------------------------ run + stats
    def _progress(self) -> int:
        """Monotone progress counter for the watchdog: tokens generated plus
        requests reaching a terminal state. Outputs survive recompute
        preemption (the replay covers them), so this never decreases — a
        flat reading across many stages means livelock, not slow work."""
        return (sum(len(r.output) for r in self._requests.values())
                + sum(1 for r in self._requests.values() if r.done))

    def _stall_msg(self, why: str, inflight: bool = False) -> str:
        stuck = sorted(r.rid for r in (list(self.scheduler.queue)
                                       + self.scheduler.prefilling
                                       + self.scheduler.running)
                       if not r.done)
        shown = ", ".join(map(str, stuck[:16])) + \
            (", ..." if len(stuck) > 16 else "")
        msg = (f"engine stalled: {why}; stuck rids=[{shown}], "
               f"queue_depth={self.scheduler.pending}, "
               f"free_slots={self.kv.free_slots}/{self.kv.max_slots}, "
               f"preemption={self.preemption}")
        if self.paged:
            msg += (f", free_pages={self.kv.free_pages}/"
                    f"{self.kv.num_pages - 1}")
        if inflight:
            msg += ", one stage in flight (dispatched, uncommitted)"
        return msg

    def run(self, requests: List[Request], *, max_stages: int = 10_000,
            stall_stages: int = 500,
            max_wall_s: Optional[float] = None) -> List[Request]:
        """Drive submitted requests to drain. A request the bounded queue
        rejects outright is finished with reason ``"rejected"`` (the batch
        keeps going); the watchdog raises a descriptive
        :class:`EngineStalledError` — instead of silently looping — when no
        stage can be formed while work remains, when ``stall_stages``
        stages pass without a token or a terminal transition, or when the
        stage/wall budget runs out with work still pending."""
        t_start = time.monotonic()
        for r in requests:
            try:
                self.submit(r)
            except AdmissionRejected:
                r.finish("rejected", self._now())
        stages = 0
        idle = 0
        last = self._progress()
        while self.scheduler.has_work:
            if stages >= max_stages:
                raise EngineStalledError(self._stall_msg(
                    f"max_stages={max_stages} exhausted with work pending"))
            if (max_wall_s is not None
                    and time.monotonic() - t_start > max_wall_s):
                raise EngineStalledError(self._stall_msg(
                    f"wall budget {max_wall_s}s exhausted"))
            if self.step() is None:
                if not self.scheduler.has_work:
                    break               # drained by the expiry sweep
                raise EngineStalledError(self._stall_msg(
                    "no stage could be formed (capacity livelock — queued "
                    "work cannot be admitted and nothing is running)"))
            stages += 1
            prog = self._progress()
            if prog > last:
                last, idle = prog, 0
            else:
                idle += 1
                if idle >= stall_stages:
                    raise EngineStalledError(self._stall_msg(
                        f"no progress across {idle} consecutive stages"))
        return requests

    #: cumulative counters stats() also reports as per-window deltas
    STATS_DELTA_KEYS = ("stages", "preemptions", "forced_evictions",
                        "stage_aborts", "retries", "shed", "expired",
                        "cancelled", "rejected", "audit_violations",
                        "shared_tokens_skipped", "spec_proposed",
                        "spec_accepted")

    def stats(self, reset: bool = False) -> dict:
        """Engine-lifetime robustness + capacity roll-up (the serve CLI and
        the overload benchmark report exactly these keys). The top-level
        counters stay cumulative; ``out["delta"]`` carries each
        :data:`STATS_DELTA_KEYS` counter's change since the last
        ``stats(reset=True)`` call, so a fleet aggregator polling N engines
        can attribute sheds/retries/aborts to its window. ``reset=True``
        snapshots the current totals as the next window's base (the
        cumulative values are never cleared). Lock-guarded: with the async
        loop running, a poll from another thread lands between commits and
        never reads a torn window."""
        with self._lock:
            out = {"stages": self._stage_idx,
                   "preemptions": self.preemptions,
                   "forced_evictions": self.forced_evictions,
                   "stage_aborts": self.stage_aborts,
                   "retries": self.retries,
                   "shed": self.shed,
                   "expired": self.expired,
                   "cancelled": self.cancelled,
                   "rejected": self.rejected,
                   "audit_violations": self.audit_violations,
                   "peak_active": self.peak_active,
                   "shared_tokens_skipped": self.shared_tokens_skipped,
                   "spec_hits": self.spec_hits,
                   "spec_misses": self.spec_misses,
                   "spec_miss_reasons": dict(self.spec_miss_reasons),
                   # speculative DECODING (PR 9) — distinct from the
                   # speculative PLANNING counters above
                   "spec_proposed": self.spec_proposed,
                   "spec_accepted": self.spec_accepted,
                   "spec_rewinds": self.spec_rewinds,
                   "spec_acceptance": (self.spec_accepted
                                       / max(self.spec_proposed, 1)),
                   "chained_stages": self.chained_stages,
                   "host_gap_s": self.host_gap_s,
                   "gap_stages": self.gap_stages,
                   "aging_promotions": self.scheduler.aging_promotions,
                   # MoE layers of the traced stage programs whose expert
                   # kernels read the weight tables in place / that
                   # gathered a copy first (XLA path, padded d_ff)
                   "moe_inplace_layers": sum(
                       t["inplace"] for t in self._moe_lowered.values()),
                   "moe_gathered_layers": sum(
                       t["gathered"] for t in self._moe_lowered.values()),
                   "kv": self.kv.stats()}
            out["delta"] = {k: out[k] - self._stats_base.get(k, 0)
                            for k in self.STATS_DELTA_KEYS}
            if reset:
                self._stats_base = {k: out[k]
                                    for k in self.STATS_DELTA_KEYS}
            if self.injector is not None:
                out["fault_counts"] = dict(self.injector.counts)
            return out
