"""jit-ready wrappers around the Pallas kernels.

These adapt model-layer layouts to kernel layouts (GQA head grouping,
block padding) and select the execution mode:

  * on the TPU backend: the Pallas kernels proper;
  * on the CPU backend (tests, ``JAX_PLATFORMS=cpu``): ``interpret=True``
    executes the kernel bodies in Python for correctness validation against
    ``ref.py``;
  * on any other backend: an error — a run that meant to use the chip never
    carries on silently through interpret mode.

The XLA fallbacks in models/attention.py remain the lowering used by the
dry-run (Pallas doesn't lower on the CPU backend); kernels are the TPU
deployment path (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attn import (decode_attention_kernel,
                                       paged_attention_kernel)
from repro.kernels.flash_attn import flash_attention_kernel
from repro.kernels.moe_gemm import moe_gemm_kernel, ragged_moe_gemm_kernel
from repro.kernels.moe_gemv import moe_gemv_kernel, ragged_moe_gemv_kernel
from repro.kernels.ssd_decode import ssd_decode_kernel


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or in interpret mode on the "
        f"CPU backend; found backend {backend!r}")


def _pad_to(x, multiple: int, axis: int):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_block: int = 256,
                    kv_block: int = 256, interpret: bool | None = None):
    """Model layout: q (B, S, H, hd); k, v (B, S, KV, hd) -> (B, S, H, hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    qpk = H // KV
    interpret = _interpret_default() if interpret is None else interpret
    q_block = min(q_block, max(S, 8))
    kv_block = min(kv_block, max(S, 8))
    # (B, KV, qpk, S, hd) / (B, KV, S, hd)
    qg = q.reshape(B, S, KV, qpk, hd).transpose(0, 2, 3, 1, 4)
    kg = k.transpose(0, 2, 1, 3)
    vg = v.transpose(0, 2, 1, 3)
    qg = _pad_to(_pad_to(qg, q_block, 3), kv_block, 3)
    kg = _pad_to(_pad_to(kg, q_block, 2), kv_block, 2)
    vg = _pad_to(_pad_to(vg, q_block, 2), kv_block, 2)
    out = flash_attention_kernel(qg, kg, vg, causal=causal, window=window,
                                 softcap=softcap, q_block=q_block,
                                 kv_block=kv_block, seq_len=S,
                                 interpret=interpret)
    out = out[:, :, :, :S]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                     softcap: float = 0.0, kv_block: int = 512,
                     interpret: bool | None = None):
    """Model layout: q (B, 1, H, hd); caches (B, Smax, KV, hd); lengths (B,).
    -> (B, 1, H, hd)."""
    B, _, H, hd = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    qpk = H // KV
    interpret = _interpret_default() if interpret is None else interpret
    kv_block = min(kv_block, max(Smax, 8))
    qg = q.reshape(B, KV, qpk, hd)
    kg = _pad_to(k_cache.transpose(0, 2, 1, 3), kv_block, 2)
    vg = _pad_to(v_cache.transpose(0, 2, 1, 3), kv_block, 2)
    out = decode_attention_kernel(qg, kg, vg, lengths.astype(jnp.int32),
                                  window=window, softcap=softcap,
                                  kv_block=kv_block, interpret=interpret)
    return out.reshape(B, 1, H, hd)


def paged_decode_attention(q, k_pages, v_pages, lengths, block_tables, *,
                           k_scales=None, v_scales=None,
                           window: int = 0, softcap: float = 0.0,
                           pages_bound: int | None = None,
                           interpret: bool | None = None):
    """Model layout: q (B, 1, H, hd); page pools (P, KV, page, hd);
    lengths (B,); block_tables (B, maxp) int32. -> (B, 1, H, hd).

    With ``k_scales``/``v_scales`` ((P, KV, page) fp32) the pools are int8
    and the kernel runs in-kernel scaled dots — streamed KV bytes halve.

    The kv grid spans the block-table width (or ``pages_bound`` if given, to
    trim a full-width table); dead pages past each sequence's live length
    cost no HBM traffic (the scalar-prefetch index map clamps them to a
    resident page)."""
    B, _, H, hd = q.shape
    KV = k_pages.shape[1]
    qpk = H // KV
    interpret = _interpret_default() if interpret is None else interpret
    qg = q.reshape(B, KV, qpk, hd)
    lengths = lengths.astype(jnp.int32)
    # a decode row is a one-position chunk at position length - 1
    out = paged_attention_kernel(qg, k_pages, v_pages, lengths, lengths - 1,
                                 block_tables, k_scale_pages=k_scales,
                                 v_scale_pages=v_scales, qpk=qpk,
                                 window=window, softcap=softcap,
                                 pages_bound=pages_bound,
                                 interpret=interpret)
    return out.reshape(B, 1, H, hd)


def chunked_prefill_attention(q, k_pages, v_pages, totals, starts,
                              block_tables, *, k_scales=None, v_scales=None,
                              softcap: float = 0.0,
                              pages_bound: int | None = None,
                              interpret: bool | None = None):
    """Model layout: q (B, Sc, H, hd) chunk queries; page pools
    (P, KV, page, hd); totals/starts (B,); block_tables (B, maxp) int32.
    -> (B, Sc, H, hd). ``k_scales``/``v_scales`` select the int8 path as in
    ``paged_decode_attention``.

    The chunk's K/V must already be written into the pool (the model layer
    writes before attending); queries then attend the block-table-addressed
    prefix + chunk with a per-position causal mask. Dead pages past each
    sequence's total length cost no HBM traffic (scalar-prefetch clamp)."""
    B, Sc, H, hd = q.shape
    KV = k_pages.shape[1]
    qpk = H // KV
    interpret = _interpret_default() if interpret is None else interpret
    # (B, KV, Sc*qpk, hd), heads innermost so row r = chunk position r // qpk
    qg = q.reshape(B, Sc, KV, qpk, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, KV, Sc * qpk, hd)
    out = paged_attention_kernel(
        qg, k_pages, v_pages, totals, starts, block_tables,
        k_scale_pages=k_scales, v_scale_pages=v_scales, qpk=qpk,
        softcap=softcap, pages_bound=pages_bound, interpret=interpret)
    out = out.reshape(B, KV, Sc, qpk, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Sc, H, hd)


# ---------------------------------------------------------------------------
# MoE paths
# ---------------------------------------------------------------------------

def expert_f_block(f: int, f_block: int = 256) -> int | None:
    """The d_ff tile of the expert kernels: ``f_block`` where it divides
    d_ff (all of d_ff when narrower), else the widest lane-aligned (128)
    tile below it that divides d_ff; None when none does, and the tables
    must be padded to ``f_block``."""
    if f <= f_block or f % f_block == 0:
        return min(f, f_block)
    for b in range(f_block - f_block % 128, 0, -128):
        if f % b == 0:
            return b
    return None


def _expert_tables(w, f_block: int):
    """(tables, f tile): the tables as given where a tile divides d_ff,
    else padded along d_ff (a copy of every row)."""
    fb = expert_f_block(w["wi_gate"].shape[2], f_block)
    if fb is not None:
        return w, fb
    return {"wi_gate": _pad_to(w["wi_gate"], f_block, 2),
            "wi_up": _pad_to(w["wi_up"], f_block, 2),
            "wo": _pad_to(w["wo"], f_block, 1)}, f_block


def moe_gemm(w, x, *, ids=None, c_block: int = 256, f_block: int = 256,
             interpret: bool | None = None):
    """Hot-expert grouped GEMM. x: (E, C, d) -> (E, C, d). ``w`` holds
    (R, d, f)/(R, f, d) tables and ``ids`` (E,) the row of each expert
    (default: row e)."""
    interpret = _interpret_default() if interpret is None else interpret
    E, C, d = x.shape
    c_block = min(c_block, C)
    wp, f_block = _expert_tables(w, f_block)
    xp = _pad_to(x, c_block, 1)
    out = moe_gemm_kernel(wp, xp, ids, c_block=c_block, f_block=f_block,
                          interpret=interpret)
    return out[:, :C]


def ragged_moe_gemm(w, x, counts, *, ids=None, c_block: int = 256,
                    f_block: int = 256, blocks_bound: int | None = None,
                    interpret: bool | None = None):
    """Count-aware hot-expert grouped GEMM. x: (E, C, d) slot buffers (live
    tokens a contiguous prefix of the C dim); counts: (E,) live tokens per
    expert; ``w``/``ids`` as in ``moe_gemm``. Streamed weight bytes and
    FLOPs scale with live token blocks; slots at or past each expert's
    count come back zeroed. -> (E, C, d)."""
    interpret = _interpret_default() if interpret is None else interpret
    E, C, d = x.shape
    c_block = min(c_block, C)
    wp, f_block = _expert_tables(w, f_block)
    xp = _pad_to(x, c_block, 1)
    if blocks_bound is not None:     # a bound past the buffer is a no-op
        blocks_bound = min(blocks_bound, xp.shape[1] // c_block)
    cap = C if blocks_bound is None else min(C, blocks_bound * c_block)
    counts = jnp.minimum(counts.astype(jnp.int32), cap)
    out = ragged_moe_gemm_kernel(wp, xp, counts, ids, c_block=c_block,
                                 f_block=f_block, blocks_bound=blocks_bound,
                                 interpret=interpret)[:, :C]
    # dead blocks are never written by the kernel (their output DMAs are
    # elided along with their inputs) — mask so they read as zero.
    slot = jax.lax.broadcasted_iota(jnp.int32, (E, C), 1)
    return jnp.where((slot < counts[:, None])[..., None], out, 0)


def moe_gemv(w, x, counts=None, *, ids=None, f_block: int = 256,
             interpret: bool | None = None):
    """Cold-expert gather GEMV. x: (Ec, Cc, d) -> (Ec, Cc, d); ``w``/``ids``
    as in ``moe_gemm``. With ``counts`` (Ec,) live tokens per expert, fully
    empty cold experts stream no weights (scalar-prefetch DMA elision) and
    their rows come back zeroed."""
    interpret = _interpret_default() if interpret is None else interpret
    wp, f_block = _expert_tables(w, f_block)
    if counts is None:
        return moe_gemv_kernel(wp, x, ids, f_block=f_block,
                               interpret=interpret)
    Ec, Cc, _ = x.shape
    counts = jnp.minimum(counts.astype(jnp.int32), Cc)
    out = ragged_moe_gemv_kernel(wp, x, counts, ids, f_block=f_block,
                                 interpret=interpret)
    slot = jax.lax.broadcasted_iota(jnp.int32, (Ec, Cc), 1)
    return jnp.where((slot < counts[:, None])[..., None], out, 0)


def ssd_decode(state, x, dt, a_log, b, c, d, *, h_block: int = 8,
               interpret: bool | None = None):
    """Mamba-2 decode state update (the SSM bandwidth-path kernel)."""
    interpret = _interpret_default() if interpret is None else interpret
    H = state.shape[1]
    hb = h_block
    while H % hb:
        hb -= 1
    return ssd_decode_kernel(state, x, dt, a_log, b, c, d, h_block=hb,
                             interpret=interpret)


# re-exported oracles (tests import from one place)
flash_attention_ref = ref.flash_attention_ref
decode_attention_ref = ref.decode_attention_ref
int8_decode_attention_ref = ref.int8_decode_attention_ref
moe_ffn_ref = ref.moe_ffn_ref
ragged_moe_ffn_ref = ref.ragged_moe_ffn_ref
ssd_decode_ref = ref.ssd_decode_ref
