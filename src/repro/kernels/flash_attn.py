"""Pallas TPU flash-attention (prefill/train path — the xPU-analogue kernel).

Online-softmax attention with a (B, KV, nq, nk) grid and VMEM accumulators
carried across the innermost (kv-block) grid dimension — the canonical TPU
schedule. GQA is native: the q block is (qpk, bq, hd) so each score tile is a
deg_grp-wide GEMM per KV head (paper §II-B), keeping the MXU fed even for
small bq.

Block shapes are MXU/VMEM-aligned (multiples of 128 on the lane dim, hd is a
lane multiple for all assigned archs). Causal/window block-skipping is done
with ``pl.when`` gating so off-diagonal blocks cost no FLOPs.

Validated in interpret mode against ``ref.flash_attention_ref`` (CPU
container); the TPU path compiles with the same BlockSpecs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, window: int, softcap: float, scale: float,
                  bq: int, bk: int, nk: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # block-level skip: causal => kv block must start at/before q block end;
    # window => kv block must end after the window's left edge.
    needed = k_start <= q_start + bq - 1 if causal else True
    if window > 0:
        needed = jnp.logical_and(needed, k_start + bk - 1 > q_start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (qpk, bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, 0]                              # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (qpk, bq, bk)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_len
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask[None], s, NEG_INF)
        m_old = m_ref[...]                           # (qpk, bq)
        l_old = l_ref[...]
        m_new = jnp.maximum(m_old, s.max(axis=-1))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[..., None])            # (qpk, bq, bk)
        l_ref[...] = l_old * alpha + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (qpk, bq, hd)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, q_block: int = 256,
                           kv_block: int = 256, seq_len: int | None = None,
                           interpret: bool = False):
    """q: (B, KV, qpk, S, hd); k, v: (B, KV, S, hd) — S already block-padded.
    ``seq_len`` = true (unpadded) length for masking. -> (B, KV, qpk, S, hd)
    """
    B, KV, qpk, S, hd = q.shape
    assert S % q_block == 0 and S % kv_block == 0, (S, q_block, kv_block)
    nq, nk = S // q_block, S // kv_block
    seq_len = seq_len or S
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, softcap=softcap,
        scale=scale, bq=q_block, bk=kv_block, nk=nk, seq_len=seq_len)

    return pl.pallas_call(
        kernel,
        grid=(B, KV, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qpk, q_block, hd),
                         lambda b, g, qi, ki: (b, g, 0, qi, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda b, g, qi, ki: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda b, g, qi, ki: (b, g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qpk, q_block, hd),
                               lambda b, g, qi, ki: (b, g, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qpk, q_block, hd), jnp.float32),   # acc
            pltpu.VMEM((qpk, q_block), jnp.float32),       # m
            pltpu.VMEM((qpk, q_block), jnp.float32),       # l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
