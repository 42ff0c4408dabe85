"""Decoder-block composition per LayerKind + scan-able segment stacking."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, ATTN_BIDIR, ATTN_CROSS, ATTN_LOCAL,
                                DENSE, MAMBA, MOE, NONE, LayerKind, ModelConfig,
                                Segment)
from repro.models import attention as attn_mod
from repro.models.attention import (AttnCall, attention_decode_step,
                                    attention_forward, attention_specs,
                                    cross_attention_forward, cross_kv)
from repro.models.ffn import ffn_apply, ffn_specs
from repro.models.layers import rmsnorm, rmsnorm_specs
from repro.core.execution import EXPERT_TABLES, moe_execute
from repro.models.moe import moe_specs
from repro.models.param import stack_specs
from repro.models.ssm import (mamba_decode_step, mamba_forward,
                              mamba_init_cache, mamba_specs)


def block_specs(cfg: ModelConfig, kind: LayerKind) -> dict:
    d = cfg.d_model
    pdtype = cfg.param_dtype
    specs: Dict[str, Any] = {"norm1": rmsnorm_specs(d, pdtype)}
    if kind.mixer == MAMBA:
        specs["mixer"] = mamba_specs(cfg)
    else:
        specs["mixer"] = attention_specs(cfg)
    if kind.mixer == ATTN_CROSS:
        specs["cross"] = attention_specs(cfg)
        specs["norm_cross"] = rmsnorm_specs(d, pdtype)
    if kind.ffn != NONE and not cfg.parallel_block:
        specs["norm2"] = rmsnorm_specs(d, pdtype)
    if kind.ffn == DENSE:
        specs["ffn"] = ffn_specs(cfg)
    elif kind.ffn == MOE:
        specs["ffn"] = moe_specs(cfg)
    return specs


def _attn_call(cfg: ModelConfig, kind: LayerKind) -> AttnCall:
    from repro.core.execution import current_plan
    plan = current_plan()
    kw = dict(q_block=plan.attn_q_block, kv_block=plan.attn_kv_block,
              score_bf16=plan.attn_score_bf16)
    if kind.mixer == ATTN_LOCAL:
        return AttnCall(causal=True, window=cfg.sliding_window, **kw)
    if kind.mixer == ATTN_BIDIR:
        return AttnCall(causal=False, **kw)
    return AttnCall(causal=True, **kw)


def block_forward(params, cfg: ModelConfig, kind: LayerKind, x, positions,
                  *, segment_ids=None, enc_out=None,
                  enc_segment_ids=None):
    """Train/prefill path. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    if kind.mixer == MAMBA:
        mixer_out = mamba_forward(params["mixer"], cfg, h)
    else:
        mixer_out = attention_forward(params["mixer"], cfg, h, positions,
                                      _attn_call(cfg, kind),
                                      segment_ids=segment_ids)
    if cfg.parallel_block and kind.ffn != NONE:
        # command-r style: attn and ffn share the pre-norm input
        if kind.ffn == MOE:
            ffn_out, aux = moe_execute(params["ffn"], cfg, h)
        else:
            ffn_out = ffn_apply(params["ffn"], h)
        return x + mixer_out + ffn_out, aux
    x = x + mixer_out
    if kind.mixer == ATTN_CROSS:
        h = rmsnorm(params["norm_cross"], x, cfg.norm_eps)
        kv = cross_kv(params["cross"], cfg, enc_out)
        x = x + cross_attention_forward(params["cross"], cfg, h, kv,
                                        segment_ids=segment_ids,
                                        kv_segment_ids=enc_segment_ids)
    if kind.ffn == NONE:
        return x, aux
    h = rmsnorm(params["norm2"], x, cfg.norm_eps)
    if kind.ffn == MOE:
        ffn_out, aux = moe_execute(params["ffn"], cfg, h)
    else:
        ffn_out = ffn_apply(params["ffn"], h)
    return x + ffn_out, aux


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------

def block_init_cache(cfg: ModelConfig, kind: LayerKind, batch: int,
                     max_len: int, dtype, kv_quant: bool = False, *,
                     paged: bool = False, page_size: int = 64,
                     num_pages: int = 0) -> dict:
    if paged:
        # Paged layout: this layer's share of the KV page pool. No per-slot
        # leaves — slot metadata (lengths, block tables) lives in the
        # KVManager and reaches decode as `attn_ctx`. Page 0 is the reserved
        # null page (write target of padded batch rows). ATTN_LOCAL stays
        # dense: its prefill cache is a ring buffer whose slots don't map
        # positionally onto pages (the paged kernel itself supports window
        # masking for standalone use).
        if kind.mixer != ATTN:
            raise ValueError(
                f"paged KV cache supports full self-attention decoder "
                f"layers only, got mixer={kind.mixer}")
        kv = cfg.num_kv_heads
        hd = cfg.resolved_head_dim
        if kv_quant:
            # int8 value pools + fp32 per-(token, kv-head) scale pools
            # addressed by the SAME block tables (ROADMAP "DESIGN: int8 KV
            # pages"): per-token bytes drop from 2·hd·itemsize to
            # 2·(hd + 4) — the scale rider streams with its page.
            return {
                "k_pages": jnp.zeros((num_pages, kv, page_size, hd),
                                     jnp.int8),
                "v_pages": jnp.zeros((num_pages, kv, page_size, hd),
                                     jnp.int8),
                "k_scale_pages": jnp.zeros((num_pages, kv, page_size),
                                           jnp.float32),
                "v_scale_pages": jnp.zeros((num_pages, kv, page_size),
                                           jnp.float32),
            }
        return {"k_pages": jnp.zeros((num_pages, kv, page_size, hd), dtype),
                "v_pages": jnp.zeros((num_pages, kv, page_size, hd), dtype)}
    if kind.mixer == MAMBA:
        return {"mamba": mamba_init_cache(cfg, batch, dtype)}
    window = cfg.sliding_window if kind.mixer == ATTN_LOCAL else 0
    # ring buffer (window + 1 dump slot) for local layers — bounds long-context
    # KV memory at O(window) instead of O(seq_len)
    size = min(max_len, window) + 1 if window > 0 else max_len
    kv = cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    kv_dtype = jnp.int8 if kv_quant else dtype
    cache = {
        "k": jnp.zeros((batch, size, kv, hd), kv_dtype),
        "v": jnp.zeros((batch, size, kv, hd), kv_dtype),
        "pos": jnp.full((batch, size), jnp.iinfo(jnp.int32).max, jnp.int32),
        "len": jnp.zeros((batch,), jnp.int32),
    }
    if kv_quant:
        cache["k_scale"] = jnp.zeros((batch, size, kv), jnp.float32)
        cache["v_scale"] = jnp.zeros((batch, size, kv), jnp.float32)
    if kind.mixer == ATTN_CROSS:
        # cross-attention KV stays full-precision (written once per request)
        cache["cross_k"] = jnp.zeros((batch, max_len, kv, hd), dtype)
        cache["cross_v"] = jnp.zeros((batch, max_len, kv, hd), dtype)
        cache["cross_len"] = jnp.zeros((batch,), jnp.int32)
    return cache


def block_decode_step(params, cfg: ModelConfig, kind: LayerKind, x, cache,
                      attn_ctx=None, collect_counts: bool = False,
                      layer=None):
    """Single-token decode. Returns (x, new_cache, moe_counts). ``attn_ctx``
    carries the stage's slot metadata ({"lengths", "block_tables"} for paged
    caches; optional "valid" (B,) live-row mask excluding padded/dead rows
    from MoE routing counts and capacity). ``moe_counts`` is the layer's
    per-expert routed-token counts ((E,) fp32) when ``collect_counts`` and
    the block has an MoE ffn, else None — the serving engine feeds the
    actual counts (not a synthetic draw) back to the Duplex planner.
    ``layer`` as in ``moe_execute``: the MoE expert tables in ``params``
    are the segment's stacks."""
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    valid = attn_ctx.get("valid") if attn_ctx else None
    counts = None

    def _ffn(h_in):
        nonlocal counts
        if kind.ffn != MOE:
            return ffn_apply(params["ffn"], h_in)
        out, stats = moe_execute(params["ffn"], cfg, h_in, return_stats=True,
                                 token_valid=valid, layer=layer)
        if collect_counts:
            counts = stats.counts.astype(jnp.float32)
        return out

    if kind.mixer == MAMBA:
        mixer_out, new_mamba = mamba_decode_step(params["mixer"], cfg, h,
                                                 cache["mamba"])
        new_cache = {"mamba": new_mamba}
    elif "k_pages" in cache:
        from repro.models.attention import paged_attention_decode_step
        window = cfg.sliding_window if kind.mixer == ATTN_LOCAL else 0
        mixer_out, new_cache = paged_attention_decode_step(
            params["mixer"], cfg, h, cache, attn_ctx, window=window)
    else:
        window = cfg.sliding_window if kind.mixer == ATTN_LOCAL else 0
        mixer_out, new_attn = attention_decode_step(params["mixer"], cfg, h,
                                                    cache, window=window)
        new_cache = dict(cache)
        new_cache.update(new_attn)
    if cfg.parallel_block and kind.ffn != NONE:
        ffn_out = _ffn(h)
        return x + mixer_out + ffn_out, new_cache, counts
    x = x + mixer_out
    if kind.mixer == ATTN_CROSS:
        h = rmsnorm(params["norm_cross"], x, cfg.norm_eps)
        from repro.models.attention import decode_attention
        B = x.shape[0]
        hd = cfg.resolved_head_dim
        q = jnp.einsum("bsd,dh->bsh", h, params["cross"]["wq"]["kernel"])
        q = q.reshape(B, 1, cfg.num_heads, hd)
        if cfg.qk_norm:
            q = rmsnorm(params["cross"]["q_norm"], q, cfg.norm_eps)
        out = decode_attention(q, cache["cross_k"], cache["cross_v"],
                               cache["cross_len"])
        x = x + jnp.einsum("bsh,hd->bsd", out.reshape(B, 1, -1),
                           params["cross"]["wo"]["kernel"])
    if kind.ffn == NONE:
        return x, new_cache, counts
    h = rmsnorm(params["norm2"], x, cfg.norm_eps)
    ffn_out = _ffn(h)
    return x + ffn_out, new_cache, counts


def block_prefill(params, cfg: ModelConfig, kind: LayerKind, x, positions,
                  true_len, cache, *, segment_ids=None, enc_out=None):
    """Prefill path: like block_forward but also populates the decode cache.
    x: (B, S, d); true_len: (B,) valid prompt lengths. Returns (x, new_cache)."""
    from repro.models.attention import (write_prefill_cache, _project_qkv)
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    new_cache = dict(cache)
    if kind.mixer == MAMBA:
        mixer_out, mcache = mamba_forward(params["mixer"], cfg, h,
                                          return_state=True)
        new_cache = {"mamba": mcache}
    else:
        window = cfg.sliding_window if kind.mixer == ATTN_LOCAL else 0
        call = _attn_call(cfg, kind)
        mixer_out, (k, v) = attention_forward(params["mixer"], cfg, h,
                                              positions, call,
                                              segment_ids=segment_ids,
                                              return_kv=True)
        new_cache.update(write_prefill_cache(cache, k, v, true_len,
                                             window=window))
    if cfg.parallel_block and kind.ffn != NONE:
        # must match block_forward exactly: attn and ffn share pre-norm input
        if kind.ffn == MOE:
            ffn_out, _ = moe_execute(params["ffn"], cfg, h)
        else:
            ffn_out = ffn_apply(params["ffn"], h)
        return x + mixer_out + ffn_out, new_cache
    x = x + mixer_out
    if kind.mixer == ATTN_CROSS:
        h = rmsnorm(params["norm_cross"], x, cfg.norm_eps)
        ck, cv = cross_kv(params["cross"], cfg, enc_out)
        x = x + cross_attention_forward(params["cross"], cfg, h, (ck, cv),
                                        segment_ids=segment_ids)
        new_cache["cross_k"] = ck.astype(cache["cross_k"].dtype)
        new_cache["cross_v"] = cv.astype(cache["cross_v"].dtype)
        new_cache["cross_len"] = jnp.full_like(true_len, ck.shape[1])
    if kind.ffn == NONE:
        return x, new_cache
    h = rmsnorm(params["norm2"], x, cfg.norm_eps)
    if kind.ffn == MOE:
        ffn_out, _ = moe_execute(params["ffn"], cfg, h)
    else:
        ffn_out = ffn_apply(params["ffn"], h)
    return x + ffn_out, new_cache


# ---------------------------------------------------------------------------
# Segments (scan over stacked super-blocks)
# ---------------------------------------------------------------------------

def segment_specs(cfg: ModelConfig, seg: Segment) -> dict:
    one = {"blocks": tuple(block_specs(cfg, k) for k in seg.pattern)}
    return stack_specs(one, seg.repeats)


def segment_forward(params, cfg: ModelConfig, seg: Segment, x, positions, *,
                    segment_ids=None, enc_out=None, enc_segment_ids=None,
                    remat: str = "full"):
    """scan over the segment's stacked super-blocks; returns (x, aux_sum)."""

    def super_block(x, blk_params):
        aux_total = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(seg.pattern):
            x, aux = block_forward(blk_params["blocks"][i], cfg, kind, x,
                                   positions, segment_ids=segment_ids,
                                   enc_out=enc_out,
                                   enc_segment_ids=enc_segment_ids)
            aux_total = aux_total + aux
        return x, aux_total

    if remat == "full":
        super_block = jax.checkpoint(super_block,
                                     policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        super_block = jax.checkpoint(
            super_block,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    def body(x, blk_params):
        return super_block(x, blk_params)

    x, auxs = jax.lax.scan(body, x, params)
    return x, auxs.sum()


def segment_init_cache(cfg: ModelConfig, seg: Segment, batch: int,
                       max_len: int, dtype, kv_quant: bool = False, *,
                       paged: bool = False, page_size: int = 64,
                       num_pages: int = 0):
    one = {"blocks": tuple(block_init_cache(cfg, k, batch, max_len, dtype,
                                            kv_quant, paged=paged,
                                            page_size=page_size,
                                            num_pages=num_pages)
                           for k in seg.pattern)}
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (seg.repeats,) + a.shape).copy(), one)


def _split_expert_tables(params, seg: Segment):
    """(scanned, tables): the serving scans slice ``scanned`` per layer,
    while each MoE block's expert tables (``tables[i]``, {} for other
    blocks) enter the scan body whole, as constants. The expert kernels
    address (layer, expert) rows of the stacks in place, so no per-layer
    slice of the tables is made."""
    scanned, tables = [], []
    for blk, kind in zip(params["blocks"], seg.pattern):
        t = {}
        if kind.ffn == MOE:
            t = {k: v for k, v in blk["ffn"].items() if k in EXPERT_TABLES}
            blk = {**blk, "ffn": {k: v for k, v in blk["ffn"].items()
                                  if k not in EXPERT_TABLES}}
        scanned.append(blk)
        tables.append(t)
    return {"blocks": tuple(scanned)}, tuple(tables)


def _block_params(blk_params, tables, i: int):
    blk = blk_params["blocks"][i]
    return {**blk, "ffn": {**blk["ffn"], **tables[i]}} if tables[i] else blk


def segment_decode_step(params, cfg: ModelConfig, seg: Segment, x, cache,
                        attn_ctx=None, collect_counts: bool = False):
    """With ``collect_counts`` also returns the segment's summed per-expert
    MoE routing counts ((E,) fp32, zeros if the segment has no MoE)."""
    E = cfg.moe.num_experts if (collect_counts and cfg.moe) else 0
    scanned, tables = _split_expert_tables(params, seg)

    def body(x, inp):
        blk_params, blk_cache, layer = inp
        new_caches = []
        counts = jnp.zeros((E,), jnp.float32)
        for i, kind in enumerate(seg.pattern):
            x, nc, cnt = block_decode_step(
                _block_params(blk_params, tables, i), cfg, kind, x,
                blk_cache["blocks"][i], attn_ctx=attn_ctx,
                collect_counts=collect_counts, layer=layer)
            new_caches.append(nc)
            if cnt is not None and E:
                counts = counts + cnt
        return x, ({"blocks": tuple(new_caches)}, counts)

    layers = jnp.arange(seg.repeats, dtype=jnp.int32)
    x, (new_cache, counts) = jax.lax.scan(body, x, (scanned, cache, layers))
    if collect_counts:
        return x, new_cache, counts.sum(axis=0)
    return x, new_cache


# ---------------------------------------------------------------------------
# Unified mixed stage: decode rows + prefill-chunk rows in one token stream
# (ROADMAP "DESIGN: chunked prefill"). Attention runs per group (decode
# kernel vs chunked-prefill path against the same cache); norms/FFN/MoE run
# over the concatenated token stream, so the count-threaded ragged duplex
# MoE covers BOTH halves of the stage.
# ---------------------------------------------------------------------------

def block_mixed_step(params, cfg: ModelConfig, kind: LayerKind, xd, xc,
                     cache, attn_ctx, chunk_ctx,
                     collect_counts: bool = False, layer=None):
    """One block of a unified mixed stage.

    xd: (Bd, 1, d) decode rows; xc: (Bc, Sc, d) prefill-chunk rows. The
    decode half writes/attends first, then the chunk half writes its span
    into the same cache (disjoint slots; on the dense layout the decode
    half's speculative write into a mid-prefill slot is overwritten by that
    slot's chunk, which starts exactly at its length). Full self-attention
    mixers only. ``layer`` as in ``block_decode_step``. Returns (xd, xc,
    new_cache, moe_counts)."""
    from repro.models.attention import (attention_chunk_step,
                                        attention_decode_step,
                                        paged_attention_chunk_step,
                                        paged_attention_decode_step)
    if kind.mixer != ATTN:
        raise ValueError(
            f"unified mixed stages support full self-attention decoder "
            f"layers only, got mixer={kind.mixer}")
    Bd = xd.shape[0]
    Bc, Sc, d = xc.shape
    h_d = rmsnorm(params["norm1"], xd, cfg.norm_eps)
    h_c = rmsnorm(params["norm1"], xc, cfg.norm_eps)
    if "k_pages" in cache:
        mixer_d, cache_d = paged_attention_decode_step(
            params["mixer"], cfg, h_d, cache, attn_ctx)
        mixer_c, new_cache = paged_attention_chunk_step(
            params["mixer"], cfg, h_c, cache_d, chunk_ctx)
    else:
        mixer_d, upd = attention_decode_step(params["mixer"], cfg, h_d,
                                             cache)
        cache_d = dict(cache)
        cache_d.update(upd)
        mixer_c, new_cache = attention_chunk_step(params["mixer"], cfg, h_c,
                                                  cache_d, chunk_ctx)
    counts = None
    if cfg.parallel_block and kind.ffn != NONE:
        ffn_in_d, ffn_in_c = h_d, h_c
        base_d, base_c = xd + mixer_d, xc + mixer_c
    else:
        xd = xd + mixer_d
        xc = xc + mixer_c
        if kind.ffn == NONE:
            return xd, xc, new_cache, counts
        ffn_in_d = rmsnorm(params["norm2"], xd, cfg.norm_eps)
        ffn_in_c = rmsnorm(params["norm2"], xc, cfg.norm_eps)
        base_d, base_c = xd, xc
    flat = jnp.concatenate([ffn_in_d.reshape(Bd, d),
                            ffn_in_c.reshape(Bc * Sc, d)], axis=0)
    if kind.ffn == MOE:
        dec_valid = attn_ctx.get("valid") if attn_ctx else None
        if dec_valid is None:
            dec_valid = jnp.ones((Bd,), bool)
        chunk_valid = (jnp.arange(Sc, dtype=jnp.int32)[None]
                       < chunk_ctx["chunk_lens"][:, None].astype(jnp.int32))
        valid = jnp.concatenate([dec_valid, chunk_valid.reshape(-1)])
        y, stats = moe_execute(params["ffn"], cfg, flat, return_stats=True,
                               token_valid=valid, layer=layer)
        if collect_counts:
            counts = stats.counts.astype(jnp.float32)
    else:
        y = ffn_apply(params["ffn"], flat)
    yd = y[:Bd].reshape(Bd, 1, d)
    yc = y[Bd:].reshape(Bc, Sc, d)
    return base_d + yd, base_c + yc, new_cache, counts


def segment_mixed_step(params, cfg: ModelConfig, seg: Segment, xd, xc,
                       cache, attn_ctx, chunk_ctx,
                       collect_counts: bool = False):
    """Scan the segment's stacked super-blocks over both row groups.
    Returns (xd, xc, new_cache, counts) — counts summed over layers."""
    E = cfg.moe.num_experts if (collect_counts and cfg.moe) else 0
    scanned, tables = _split_expert_tables(params, seg)

    def body(carry, inp):
        xd, xc = carry
        blk_params, blk_cache, layer = inp
        new_caches = []
        counts = jnp.zeros((E,), jnp.float32)
        for i, kind in enumerate(seg.pattern):
            xd, xc, nc, cnt = block_mixed_step(
                _block_params(blk_params, tables, i), cfg, kind, xd, xc,
                blk_cache["blocks"][i], attn_ctx, chunk_ctx,
                collect_counts=collect_counts, layer=layer)
            new_caches.append(nc)
            if cnt is not None and E:
                counts = counts + cnt
        return (xd, xc), ({"blocks": tuple(new_caches)}, counts)

    layers = jnp.arange(seg.repeats, dtype=jnp.int32)
    (xd, xc), (new_cache, counts) = jax.lax.scan(body, (xd, xc),
                                                 (scanned, cache, layers))
    return xd, xc, new_cache, counts.sum(axis=0)


def segment_prefill(params, cfg: ModelConfig, seg: Segment, x, positions,
                    true_len, cache, *, segment_ids=None, enc_out=None):
    def body(x, inp):
        blk_params, blk_cache = inp
        new_caches = []
        for i, kind in enumerate(seg.pattern):
            x, nc = block_prefill(blk_params["blocks"][i], cfg, kind, x,
                                  positions, true_len, blk_cache["blocks"][i],
                                  segment_ids=segment_ids, enc_out=enc_out)
            new_caches.append(nc)
        return x, {"blocks": tuple(new_caches)}

    x, new_cache = jax.lax.scan(body, x, (params, cache))
    return x, new_cache
