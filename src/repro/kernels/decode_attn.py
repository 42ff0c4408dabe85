"""Pallas TPU flash-decode GQA kernels (the Logic-PIM-analogue attention path).

One new query token per sequence against a long KV cache: Op/B ≈ 2·deg_grp
(paper §III-A) — bandwidth-bound. The kernel's job is therefore to *stream*
exactly the live K/V bytes from HBM through VMEM once at full bandwidth; the
(qpk × bk) score GEMM rides along.

Two kernels:

  * ``decode_attention_kernel`` — dense layout (B, KV, S, hd). Per-sequence
    lengths are a scalar-prefetch operand that gates the *compute* via
    ``pl.when`` — but the BlockSpec pipeline still DMAs every kv block from
    HBM, so per-stage traffic scales with the configured maximum S, not the
    live context. Kept as the reference/fallback path.

  * ``paged_attention_kernel`` — paged layout: K/V live in a shared page
    pool (P, KV, page, hd) addressed through per-sequence block tables. One
    kernel serves both paged paths: a decode row is a one-position chunk
    (``start = length - 1``), and a chunked-prefill row scores a whole
    chunk's queries against each page with a per-position causal mask, so
    one pass covers the written prefix AND the in-flight chunk. Totals,
    starts and block tables are **scalar-prefetch** operands
    (``pltpu.PrefetchScalarGridSpec``), so the kv index map can (a) translate
    the kv grid step through the block table and (b) clamp out-of-range
    steps to an already-resident page index. Pallas elides the DMA when
    consecutive grid steps map to the same block, so dead pages past a
    sequence's live length (or before its attention window) cost **zero**
    HBM traffic — the per-stage streamed bytes scale with actual context
    lengths. The grid's kv extent is the block-table width: the serving
    engine trims it by slicing block tables to the stage's bucketed max live
    page count; a caller holding full-width tables can trim with
    ``pages_bound`` instead.

A bf16 grid step covers one kv head of one page. An int8 step covers a
group of ``_head_group(KV)`` kv heads: the scale pools are (P, KV, page)
fp32, and the TPU tiling accepts a (1, G, page) block of them only when G is
a multiple of 8 or all of KV. The group multiplies the step's VMEM (query,
output and accumulator blocks) by G, so bf16 does not pay it.

int8 KV pages (ROADMAP "DESIGN: int8 KV pages"): the paged kernel accepts
int8 K/V pools plus fp32 per-(token, kv-head) scale pools riding through the
SAME block-table index maps (so dead-page DMA clamp-elision covers the scale
stream too). Quantization never leaves the kernel: QK^T runs as an int8×int8
dot with int32 accumulation (q quantized per row over hd in VMEM), scales
folded outside the dot — exact, since the per-token scale is constant along
the contracted hd dim; PV folds the v scales into the probability rows,
re-quantizes them, and runs a second int8 dot. No fp16/fp32 copy of the
cache ever materializes in VMEM, so streamed KV bytes per page are
``2·KV·page·(hd·1B + 4B scale)`` instead of ``2·KV·page·hd·2B``.

Validated in interpret mode against ``ref.decode_attention_ref`` /
``ref.int8_decode_attention_ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import int8_quantize

NEG_INF = -1e30


def _quantize_rows(x):
    """x (rows, n) fp32 -> (int8 values, (rows, 1) fp32 scale over axis -1);
    delegates to the canonical recipe shared with quantize_kv."""
    return int8_quantize(x, keepdims=True)


def _int8_dot(a8, b8, dims):
    """int8 × int8 dot with int32 accumulation (MXU-native on TPU)."""
    return jax.lax.dot_general(a8, b8, dims,
                               preferred_element_type=jnp.int32)


def _head_group(kv: int) -> int:
    """KV heads per grid step: a multiple of 8, or all of them."""
    return 8 if kv > 8 and kv % 8 == 0 else kv


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, window: int, softcap: float, scale: float, bk: int,
                   nk: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    k_start = ki * bk
    # skip kv blocks entirely past the valid region (or before the window)
    needed = k_start < length
    if window > 0:
        needed = jnp.logical_and(needed, k_start + bk - 1 > length - 1 - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (qpk, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (qpk, bk)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = kpos < length
        if window > 0:
            valid = jnp.logical_and(valid, kpos > length - 1 - window)
        s = jnp.where(valid, s, NEG_INF)
        m_old = m_ref[...]                              # (qpk, 1)
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)                          # (qpk, bk)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (qpk, hd)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, lengths, *, window: int = 0,
                            softcap: float = 0.0, kv_block: int = 512,
                            interpret: bool = False):
    """q: (B, KV, qpk, hd); k, v: (B, KV, S, hd) with S % kv_block == 0;
    lengths: (B,) int32 valid KV entries. -> (B, KV, qpk, hd)."""
    B, KV, qpk, hd = q.shape
    S = k.shape[2]
    assert S % kv_block == 0, (S, kv_block)
    nk = S // kv_block
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_decode_kernel, window=window, softcap=softcap,
                               scale=scale, bk=kv_block, nk=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qpk, hd), lambda b, g, ki, lens: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda b, g, ki, lens: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda b, g, ki, lens: (b, g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qpk, hd),
                               lambda b, g, ki, lens: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qpk, hd), jnp.float32),   # acc
            pltpu.VMEM((qpk, 1), jnp.float32),    # m
            pltpu.VMEM((qpk, 1), jnp.float32),    # l
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)


# ---------------------------------------------------------------------------
# Paged attention: decode rows and chunked-prefill rows
# ---------------------------------------------------------------------------

def _paged_kernel(tot_ref, start_ref, bt_ref, q_ref, k_ref, v_ref, *refs,
                  quant: bool, window: int, softcap: float, scale: float,
                  page: int, npages: int, qpk: int, heads: int):
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    total = tot_ref[b]          # live KV entries (prefix + chunk)
    start = start_ref[b]        # first query position
    k_start = ki * page
    # dead pages (fully past the live region / before the window) skip the
    # compute here; their DMAs were already elided by the clamped index map.
    needed = k_start < total
    if window > 0:
        needed = jnp.logical_and(needed, k_start + page - 1 > start - window)

    @pl.when(needed)
    def _compute():
        rows = q_ref.shape[2]
        # row r holds query position start + r // qpk (heads innermost)
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // qpk
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        valid = jnp.logical_and(kpos <= qpos, kpos < total)
        if window > 0:
            valid = jnp.logical_and(valid, kpos > qpos - window)
        for h in range(heads):
            q = q_ref[0, h].astype(jnp.float32)        # (rows, hd)
            if quant:
                q8, q_sc = _quantize_rows(q)
                s = _int8_dot(q8, k_ref[0, h], (((1,), (1,)), ((), ())))
                # exact fold: per-token scales are constant along hd
                s = (s.astype(jnp.float32) * q_sc * ks_ref[0, h:h + 1, :]
                     * scale)                          # (rows, page)
            else:
                s = jax.lax.dot_general(
                    q, k_ref[0, h].astype(jnp.float32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(valid, s, NEG_INF)
            m_old = m_ref[h]                           # (rows, 1)
            m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            # a padding row can be fully masked within a live page: gate p
            # so exp(NEG_INF - NEG_INF) cannot alias to 1.
            p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
            if quant:
                pv8, pv_sc = _quantize_rows(p * vs_ref[0, h:h + 1, :])
                pv = _int8_dot(pv8, v_ref[0, h], (((1,), (0,)), ((), ())))
                pv = pv.astype(jnp.float32) * pv_sc    # (rows, hd)
            else:
                v = v_ref[0, h]
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = m_new

    @pl.when(ki == npages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pages, v_pages, totals, starts, block_tables,
                           *, k_scale_pages=None, v_scale_pages=None,
                           qpk: int = 1, window: int = 0, softcap: float = 0.0,
                           pages_bound: int | None = None,
                           interpret: bool = False):
    """q: (B, KV, rows, hd) queries with heads innermost (row r = query
    position ``starts[b] + r // qpk``); k_pages, v_pages: (P, KV, page, hd)
    shared page pool; totals: (B,) live KV entries per sequence (its
    queries' own K/V must already be written); starts: (B,) first query
    position; block_tables: (B, maxp) int32 page ids (row b, column j = pool
    page holding positions [j*page, (j+1)*page) of sequence b; unused
    columns must hold a valid page id — conventionally 0, the pool's
    reserved null page). A decode row is ``rows == qpk`` with
    ``starts == totals - 1``; a padded row has ``totals == 0``.

    With ``k_scale_pages``/``v_scale_pages`` ((P, KV, page) fp32 per-(token,
    kv-head) scales) the pools are int8 and the kernel runs the in-kernel
    scaled dots; scale blocks ride the same clamped block-table index map,
    so dead pages elide their scale DMAs along with their K/V DMAs.

    The kv grid extent is ``pages_bound`` (defaults to maxp — pass it to
    trim a full-width table without slicing it). Out-of-range grid steps are
    clamped by the scalar-prefetch index map to the sequence's last live
    page (or its first in-window page), so their DMAs are elided by the
    Pallas pipeline. Returns (B, KV, rows, hd)."""
    B, KV, rows, hd = q.shape
    P, KVp, page, hdp = k_pages.shape
    assert (KVp, hdp) == (KV, hd), (k_pages.shape, q.shape)
    assert rows % qpk == 0, (rows, qpk)
    quant = k_scale_pages is not None
    assert quant == (v_scale_pages is not None), "need both scale pools"
    if quant:
        assert k_pages.dtype == jnp.int8, k_pages.dtype
        assert k_scale_pages.shape == (P, KV, page), k_scale_pages.shape
    maxp = block_tables.shape[1]
    npages = maxp if pages_bound is None else pages_bound
    assert 1 <= npages <= maxp, (npages, maxp)
    G = _head_group(KV) if quant else 1
    kernel = functools.partial(_paged_kernel, quant=quant, window=window,
                               softcap=softcap, scale=1.0 / math.sqrt(hd),
                               page=page, npages=npages, qpk=qpk, heads=G)

    def q_map(b, g, ki, tot, st, bt):
        del ki, tot, st, bt
        return (b, g, 0, 0)

    def _clamped(b, ki, tot, st):
        # clamp the kv grid step into the sequence's live page range so the
        # pipeline re-targets an already-resident page (same block index as
        # the previous step -> the DMA is elided entirely).
        last = jnp.maximum((tot[b] + page - 1) // page - 1, 0)
        if window > 0:
            # page holding the first query's first in-window position: a
            # conservative lower clamp (never clamps away a page the mask
            # still needs).
            first = jnp.maximum((st[b] - window) // page, 0)
        else:
            first = 0
        return jnp.clip(ki, first, last)

    def kv_map(b, g, ki, tot, st, bt):
        return (bt[b, _clamped(b, ki, tot, st)], g, 0, 0)

    def sc_map(b, g, ki, tot, st, bt):
        return (bt[b, _clamped(b, ki, tot, st)], g, 0)

    in_specs = [pl.BlockSpec((1, G, rows, hd), q_map),
                pl.BlockSpec((1, G, page, hd), kv_map),
                pl.BlockSpec((1, G, page, hd), kv_map)]
    operands = [q, k_pages, v_pages]
    if quant:
        in_specs += [pl.BlockSpec((1, G, page), sc_map)] * 2
        operands += [k_scale_pages, v_scale_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV // G, npages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, rows, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, rows, hd), jnp.float32),   # acc
            pltpu.VMEM((G, rows, 1), jnp.float32),    # m
            pltpu.VMEM((G, rows, 1), jnp.float32),    # l
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(totals.astype(jnp.int32), starts.astype(jnp.int32),
      block_tables.astype(jnp.int32), *operands)
