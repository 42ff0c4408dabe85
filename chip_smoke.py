#!/usr/bin/env python3
"""Smoke run of the serving main path on one TPU chip.

Serves a small fixed request set through ``ServingEngine`` with the Pallas
kernels on — paged KV, chunked prefill, duplex/ragged MoE — at the
published widths of OLMoE-1B-7B (only depth is cut), once with bf16 KV
pages and once with int8 KV pages, and checks what comes out:

  * every request completes, and every output token is in the vocabulary;
  * the KV manager's audit is clean after each run;
  * each kind of served mixed-stage program holds the paged-attention
    kernel, the ragged MoE GEMM if it kept experts hot and the cold-expert
    GEMV if it sent experts cold;
  * prefill and decode logits of the kernel path are finite and agree with
    the XLA path under the same execution plan (kernels off) within a
    stated bf16 tolerance — a compiled kernel that returns wrong numbers
    fails here.

    python chip_smoke.py

It exits nonzero, printing no result, when JAX finds no TPU or any check
fails. Otherwise the last line of stdout is one JSON object naming the
device. The wall times it prints are smoke timings, not a benchmark: the
request set is fixed and tiny, so that only a few jit buckets compile.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import json
import re
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "olmoe-1b-7b"
# The whole model is 6.9 B params = 13.8 GB in bf16, which leaves no room
# for KV in the chip's 16 GB. Its layer pattern has period 1, so half the
# depth keeps every width and every kind of layer: ~3.6 B params, 7.1 GB.
LAYERS = 8
SEED = 0
# Largest |kernel - XLA| logit difference allowed, as a fraction of the
# largest |XLA| logit. Both paths run in bf16; they differ in where they
# round (the kernels keep f32 until their output, quantize per page in
# int8) and the difference compounds over the layers. A sound v5e run
# reads at most 0.0121 (bf16) and 0.0299 (int8); the limits are 2.5 times
# that. tests/test_chip_smoke.py plants attention-kernel faults (causal
# mask off by one, last page dropped, kv heads rolled) and checks that each
# one exceeds them.
LOGIT_TOL = {"bf16": 0.03, "int8": 0.075}
ATTN_KERNEL = "_paged_kernel"
HOT_KERNEL = "_ragged_moe_gemm_kernel"
COLD_KERNEL = "_ragged_moe_gemv_kernel"


@dataclasses.dataclass(frozen=True)
class SmokeShape:
    """The fixed request set and the engine's sizes."""
    n_requests: int = 12
    prompt_len: int = 320          # two prefill chunks: 256 + 64
    max_new: int = 32
    max_slots: int = 16
    max_len: int = 512
    page: int = 64
    chunk: int = 256
    # page-pool bytes, bf16 or int8 alike: params (7.1 GB), the pool, the
    # stage's new copy of the pool (the step does not donate it) and ~2.5 GB
    # of step temporaries must fit the chip's 16 GB
    kv_budget: int = 2 ** 31
    check_rows: int = 4            # requests in the kernel-vs-XLA check


class SmokeCheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckFailed(what)


class CompileCounter:
    """Programs XLA compiled (or loaded from the persistent cache), and the
    seconds that took, from JAX's own monitoring events."""

    def __init__(self):
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.programs, self.seconds, self.cache_hits


def smoke_config(arch: str = ARCH, layers: int = LAYERS):
    """``arch`` at its published widths with only the depth cut."""
    from repro.configs.base import Segment
    from repro.configs.registry import get_config
    full = get_config(arch)
    (seg,) = full.segments
    assert layers % len(seg.pattern) == 0, (layers, seg.pattern)
    return dataclasses.replace(
        full, num_layers=layers,
        segments=(Segment(seg.pattern, layers // len(seg.pattern)),)
    ).validate()


@functools.partial(jax.jit, static_argnums=(0, 1))
def _prefill_logits(cfg, plan, params, cache, tokens, block_tables):
    """Each row of ``tokens`` prefilled as one chunk from position 0, the
    way the engine's mixed stage calls the model; logits at its last
    position."""
    import jax.numpy as jnp
    from repro.core.execution import execution_plan
    from repro.models.model import mixed_step
    n, length = tokens.shape
    with execution_plan(plan):
        _, logits, cache, _ = mixed_step(
            params, cfg, jnp.zeros((1, 1), jnp.int32), tokens, cache,
            attn_ctx={"lengths": jnp.zeros((1,), jnp.int32),
                      "block_tables": jnp.zeros((1, 1), jnp.int32),
                      "valid": jnp.zeros((1,), bool)},
            chunk_ctx={"starts": jnp.zeros((n,), jnp.int32),
                       "chunk_lens": jnp.full((n,), length, jnp.int32),
                       "block_tables": block_tables})
    return logits[:, 0], cache


@functools.partial(jax.jit, static_argnums=(0, 1))
def _decode_logits(cfg, plan, params, cache, tokens, lengths, block_tables):
    """One decode step of every row, as the engine's paged decode stage
    calls the model."""
    from repro.core.execution import execution_plan
    from repro.models.model import decode_step
    with execution_plan(plan):
        logits, _ = decode_step(
            params, cfg, tokens, cache,
            attn_ctx={"lengths": lengths, "block_tables": block_tables,
                      "valid": lengths > 0})
    return logits[:, 0]


def relative_diff(r: dict) -> float:
    """A ``kernel_vs_xla`` stage entry's difference over its largest logit."""
    return r["max_abs_diff"] / max(r["max_abs_ref"], 1e-30)


def kernel_vs_xla(eng, prompts) -> dict:
    """Prefill ``prompts`` and take one decode step through the model calls
    the engine makes, under the engine's own plans, once with the kernels
    and once without; each path writes its own scratch page pool.
    The decode plan sends half the experts down the cold GEMV path."""
    import jax.numpy as jnp
    from repro.models.model import init_cache
    cfg, kv = eng.cfg, eng.kv
    n, length = len(prompts), len(prompts[0])
    npg = -(-(length + 1) // kv.page_size)
    bt = jnp.asarray(1 + np.arange(n * npg, dtype=np.int32).reshape(n, npg))
    tokens = jnp.asarray(prompts, jnp.int32)
    lengths = jnp.full((n,), length, jnp.int32)
    plans = {"prefill": eng.execution_plan_for(1 + n * length),
             "decode": eng.execution_plan_for(
                 n, k_cold=cfg.moe.num_experts // 2)}
    out = {}
    nxt = None
    for use_kernels in (True, False):
        p_plan, d_plan = (dataclasses.replace(plans[k], use_kernels=use_kernels)
                          for k in ("prefill", "decode"))
        cache = init_cache(cfg, n, kv.max_len, kv_quant=kv.kv_quant,
                           paged=True, page_size=kv.page_size,
                           num_pages=1 + n * npg)
        pre, cache = _prefill_logits(cfg, p_plan, eng.params, cache, tokens,
                                     bt)
        if nxt is None:        # both paths decode the kernel path's argmax
            nxt = jnp.argmax(pre, axis=-1).astype(jnp.int32)[:, None]
        dec = _decode_logits(cfg, d_plan, eng.params, cache, nxt, lengths,
                             bt)
        out[use_kernels] = (np.asarray(pre, np.float32),
                            np.asarray(dec, np.float32))
        del cache
    report = {}
    for i, stage in enumerate(("prefill", "decode")):
        got, ref = out[True][i], out[False][i]
        check(bool(np.isfinite(got).all()),
              f"non-finite {stage} logits on the kernel path")
        check(bool(np.isfinite(ref).all()),
              f"non-finite {stage} logits on the XLA path")
        report[stage] = {
            "max_abs_diff": float(np.abs(got - ref).max()),
            "max_abs_ref": float(np.abs(ref).max()),
            "argmax_agree": float((got.argmax(-1) == ref.argmax(-1)).mean())}
    return report


def make_prompts(cfg, shape: SmokeShape, seed: int):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size,
                        (shape.n_requests, shape.prompt_len)).tolist()


def record_stage_args(eng) -> dict:
    """Wrap the engine's stage-step call so that the first call of each
    jitted stage program records the shapes of its arguments, for lowering
    the program again after the run. Returns {program: argument shapes}."""
    calls = {}
    invoke = eng._invoke

    def recording(fn, *args):
        if fn not in calls:
            calls[fn] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        return invoke(fn, *args)

    eng._invoke = recording
    return calls


def served_kernels(eng, calls: dict) -> dict:
    """The Pallas kernels in one served mixed-stage program for each
    ``k_cold`` served: {k_cold: kernel names in its lowered text}."""
    progs = {}
    for key, fn in eng._mixed_fns.items():
        if fn in calls:
            progs.setdefault(key[0], fn)
    check(bool(progs), "no mixed-stage program was served")
    return {k_cold: sorted(set(re.findall(
                r'kernel_name = "(\w+)"', fn.lower(*calls[fn]).as_text())))
            for k_cold, fn in sorted(progs.items())}


def serve_once(eng, prompts, shape: SmokeShape, rid0: int,
               counter: CompileCounter):
    """Serve the request set to drain; check completion, vocabulary and
    the KV audit. Returns (requests, wall seconds, compiles in the run)."""
    from repro.serving.request import Request
    reqs = [Request(rid=rid0 + i, prompt=list(p),
                    max_new_tokens=shape.max_new)
            for i, p in enumerate(prompts)]
    c0 = counter.snapshot()
    t0 = time.perf_counter()
    eng.run(reqs)
    wall = time.perf_counter() - t0
    c1 = counter.snapshot()
    vocab = eng.cfg.vocab_size
    bad = [r.rid for r in reqs if not r.completed
           or len(r.output) != shape.max_new]
    check(not bad, f"requests not completed: {bad}")
    oov = [t for r in reqs for t in r.output if not 0 <= t < vocab]
    check(not oov, f"output tokens outside [0, {vocab}): {oov[:8]}")
    violations = eng.kv.audit()
    check(not violations, f"KV audit: {violations[:5]}")
    check(eng.kv.live_pages == 0, f"{eng.kv.live_pages} pages still live")
    return reqs, wall, (c1[0] - c0[0], c1[1] - c0[1], c1[2] - c0[2])


def make_engine(cfg, params, shape: SmokeShape, *, kv_quant: bool):
    """The engine as ``serve --kernels`` builds it: paged KV, chunked
    prefill, duplex ragged MoE, a page pool of ``shape.kv_budget`` bytes."""
    from repro.serving.engine import ServingEngine
    from repro.serving.kvmanager import pages_for_budget
    pages = pages_for_budget(cfg, shape.page, shape.kv_budget,
                             kv_quant=kv_quant)
    return ServingEngine(cfg, params, max_slots=shape.max_slots,
                         max_len=shape.max_len, use_kernels=True,
                         use_duplex=True, moe_ragged=True, kv_quant=kv_quant,
                         kv_layout="paged", kv_page_size=shape.page,
                         kv_num_pages=1 + pages,
                         prefill_chunk_tokens=shape.chunk)


def serve_phase(cfg, params, prompts, shape: SmokeShape, *, kv_quant: bool,
                counter: CompileCounter, log=print) -> dict:
    """One KV flavour: the kernel-vs-XLA check, then the request set served
    twice — a cold run that compiles, and a warm run that should not."""
    flavour = "int8" if kv_quant else "bf16"
    eng = make_engine(cfg, params, shape, kv_quant=kv_quant)
    kv_bytes = sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(eng.kv.cache))
    log(f"[smoke] {flavour} KV: {eng.kv.num_pages} pages x {shape.page} "
        f"tokens, {kv_bytes} bytes ({kv_bytes / 2**30:.3f} GiB)")
    rep = kernel_vs_xla(eng, prompts[:shape.check_rows])
    tol = LOGIT_TOL[flavour]
    for stage, r in rep.items():
        rel = relative_diff(r)
        log(f"[smoke] {flavour} kernel vs XLA {stage} logits "
            f"({shape.check_rows} requests): max|diff|={r['max_abs_diff']} "
            f"max|ref|={r['max_abs_ref']} rel={rel} (tol {tol}), "
            f"argmax agree={r['argmax_agree']}")
        check(rel <= tol, f"{flavour} {stage} logits: kernel vs XLA relative "
                          f"difference {rel} > {tol}")
    calls = record_stage_args(eng)
    runs = {}
    for label, rid0 in (("cold", 0), ("warm", len(prompts))):
        reqs, wall, (n_c, s_c, hits) = serve_once(eng, prompts, shape, rid0,
                                                  counter)
        n_tok = sum(len(r.output) for r in reqs)
        runs[label] = {"wall_s": wall, "tokens": n_tok, "compiles": n_c,
                       "compile_s": s_c, "cache_hits": hits,
                       "outputs": [list(r.output) for r in reqs]}
        log(f"[smoke] {flavour} {label} run: {len(reqs)}/{len(reqs)} "
            f"requests done, {n_tok} tokens out, {n_c} programs compiled "
            f"({s_c:.1f} s, {hits} persistent-cache hits), smoke timing "
            f"(not a benchmark): wall {wall:.3f} s")
    stats = eng.stats()
    k_colds = dict(sorted(collections.Counter(
        r.k_cold for r in eng.reports).items()))
    kernels = served_kernels(eng, calls)
    log(f"[smoke] {flavour} stages={stats['stages']} "
        f"peak concurrent batch={stats['peak_active']}; served stages by "
        f"k_cold (experts sent to the cold GEMV): {k_colds}")
    log(f"[smoke] {flavour} Pallas kernels in served mixed-stage programs "
        f"by k_cold: {kernels}")
    return {"kv_bytes": kv_bytes, "check": rep, "runs": runs,
            "stage_k_cold": k_colds, "kernels": kernels}


def check_kernels(flavour: str, kernels: dict, num_experts: int) -> None:
    """The compiled kernels, not an XLA fallback, served the stages."""
    for k_cold, names in kernels.items():
        want = ({ATTN_KERNEL}
                | ({HOT_KERNEL} if k_cold < num_experts else set())
                | ({COLD_KERNEL} if k_cold > 0 else set()))
        missing = want - set(names)
        check(not missing, f"{flavour}: the served stage program with "
                           f"k_cold={k_cold} lacks {sorted(missing)}")


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} devices",
              file=sys.stderr)
        return 1
    from repro.configs.registry import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.model import init_model
    cache_dir = use_compile_cache()
    counter = CompileCounter()
    print(f"[smoke] device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")
    cfg = smoke_config()
    m = cfg.moe
    print(f"[smoke] {cfg.name} at published widths: d_model {cfg.d_model}, "
          f"{cfg.num_heads}/{cfg.num_kv_heads} heads x "
          f"{cfg.resolved_head_dim}, {m.num_experts} experts top-{m.top_k} "
          f"d_ff_expert {m.d_ff_expert}, vocab {cfg.vocab_size}, "
          f"{cfg.param_dtype}; depth cut to {cfg.num_layers} of "
          f"{get_config(ARCH).num_layers} layers (the full model's "
          f"13.8 GB of bf16 weights leave no room for KV in 16 GB)")
    t0 = time.perf_counter()
    params = jax.jit(init_model, static_argnums=1)(jax.random.PRNGKey(SEED),
                                                   cfg)
    leaves = jax.tree_util.tree_leaves(params)
    jax.block_until_ready(leaves)
    n_params = sum(x.size for x in leaves)
    p_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    print(f"[smoke] params: {n_params} ({p_bytes} bytes), random from seed "
          f"{SEED}, made in {time.perf_counter() - t0:.1f} s")
    shape = SmokeShape()
    prompts = make_prompts(cfg, shape, SEED)
    print(f"[smoke] requests: {shape.n_requests} x {shape.prompt_len} "
          f"prompt tokens, {shape.max_new} new tokens each; chunk "
          f"{shape.chunk}, page {shape.page}, {shape.max_slots} slots")
    for kv_quant in (False, True):
        out = serve_phase(cfg, params, prompts, shape, kv_quant=kv_quant,
                          counter=counter)
        check_kernels("int8" if kv_quant else "bf16", out["kernels"],
                      cfg.moe.num_experts)
        gc.collect()
        peak = dev.memory_stats().get("peak_bytes_in_use")
        print(f"[smoke] peak device memory so far: {peak} bytes")
    print(f"[smoke] compiles in all: {counter.programs} programs, "
          f"{counter.seconds:.1f} s ({counter.cache_hits} persistent-cache "
          f"hits)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
