"""Pallas TPU cold-expert gather-GEMV kernel (the Logic-PIM-analogue MoE path).

Cold experts serve only a handful of tokens (paper §V-B: "experts with
relatively fewer tokens are processed in Logic-PIM"), so their FFN is
bandwidth-bound: ~1-8 Op/B — weights dominate the traffic. This kernel is
laid out to stream each cold expert's 3 weight matrices HBM->VMEM exactly
once, with the tiny token slab (C_cold × d) resident in VMEM for the whole
pass. Grid (E_cold, nF): no token-block dimension (the token slab is one
block), f is streamed in lane-aligned tiles.

Compared to running cold experts through the grouped-GEMM path, this removes
the capacity padding: the padded-dense path pads every expert to C_hot rows,
so a 2-token expert burns C_hot/2× its useful FLOPs; here it burns
C_cold/2×, with C_cold sized to the tail (default 8).

``ragged_moe_gemv_kernel`` additionally takes per-expert live token counts
as a scalar-prefetch operand: fully *empty* cold experts (common under
fluctuating continuous-batching routing — the cold set is the k_cold
least-loaded ranks) have their weight DMAs elided by clamped index maps and
their compute skipped, so cold-path weight traffic scales with the number of
*occupied* cold experts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_gemv_kernel(x_ref, wg_ref, wu_ref, wo_ref, o_ref, acc_ref, *,
                     nf: int):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                     # (Cc, d) — stays in VMEM
    wg = wg_ref[0]                                   # (d, bf) — streamed
    wu = wu_ref[0]
    wo = wo_ref[0]                                   # (bf, d) — streamed
    g = jax.lax.dot(x, wg, preferred_element_type=jnp.float32)   # (Cc, bf)
    u = jax.lax.dot(x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_ref[...] += jax.lax.dot(h, wo, preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gemv_kernel(w, x, *, f_block: int = 256, interpret: bool = False):
    """w: dict wi_gate/wi_up (Ec, d, f), wo (Ec, f, d); x: (Ec, Cc, d) with a
    small Cc. f % f_block == 0 (ops.py pads). -> (Ec, Cc, d)."""
    Ec, Cc, d = x.shape
    f = w["wi_gate"].shape[2]
    f_block = min(f_block, f)
    assert f % f_block == 0, (f, f_block)
    nf = f // f_block

    kernel = functools.partial(_moe_gemv_kernel, nf=nf)

    return pl.pallas_call(
        kernel,
        grid=(Ec, nf),
        in_specs=[
            pl.BlockSpec((1, Cc, d), lambda e, fi: (e, 0, 0)),
            pl.BlockSpec((1, d, f_block), lambda e, fi: (e, 0, fi)),
            pl.BlockSpec((1, d, f_block), lambda e, fi: (e, 0, fi)),
            pl.BlockSpec((1, f_block, d), lambda e, fi: (e, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, Cc, d), lambda e, fi: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Ec, Cc, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((Cc, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w["wi_gate"], w["wi_up"], w["wo"])


# ---------------------------------------------------------------------------
# Ragged (count-aware, scalar-prefetch) gather GEMV
# ---------------------------------------------------------------------------

def _ragged_moe_gemv_kernel(cnt_ref, lle_ref, x_ref, wg_ref, wu_ref, wo_ref,
                            o_ref, acc_ref, *, nf: int):
    e = pl.program_id(0)
    fi = pl.program_id(1)
    live = cnt_ref[e] > 0

    @pl.when(live & (fi == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        x = x_ref[0]                                 # (Cc, d)
        g = jax.lax.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jax.lax.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        acc_ref[...] += jax.lax.dot(h, wo_ref[0],
                                    preferred_element_type=jnp.float32)

    @pl.when(live & (fi == nf - 1))
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def ragged_moe_gemv_kernel(w, x, counts, *, f_block: int = 256,
                           interpret: bool = False):
    """Like ``moe_gemv_kernel`` but empty experts (counts[e] == 0) stream no
    weights: the index maps clamp them to the nearest preceding occupied
    expert's resident blocks (DMA elided) and compute is skipped. counts:
    (Ec,) int32. Empty experts' output rows come back zeroed via the ops.py
    wrapper mask. -> (Ec, Cc, d)."""
    Ec, Cc, d = x.shape
    f = w["wi_gate"].shape[2]
    f_block = min(f_block, f)
    assert f % f_block == 0, (f, f_block)
    nf = f // f_block
    counts = counts.astype(jnp.int32)
    idx = jnp.where(counts > 0, jnp.arange(Ec, dtype=jnp.int32), -1)
    lle = jnp.maximum(jax.lax.cummax(idx, axis=0), 0).astype(jnp.int32)

    kernel = functools.partial(_ragged_moe_gemv_kernel, nf=nf)

    def x_map(e, fi, cnt, lle):
        del fi
        return (jnp.where(cnt[e] > 0, e, lle[e]), 0, 0)

    def wi_map(e, fi, cnt, lle):
        live = cnt[e] > 0
        return (jnp.where(live, e, lle[e]), 0,
                jnp.where(live, fi, nf - 1))

    def wo_map(e, fi, cnt, lle):
        live = cnt[e] > 0
        return (jnp.where(live, e, lle[e]),
                jnp.where(live, fi, nf - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Ec, nf),
        in_specs=[
            pl.BlockSpec((1, Cc, d), x_map),
            pl.BlockSpec((1, d, f_block), wi_map),
            pl.BlockSpec((1, d, f_block), wi_map),
            pl.BlockSpec((1, f_block, d), wo_map),
        ],
        out_specs=pl.BlockSpec((1, Cc, d), x_map),
        scratch_shapes=[pltpu.VMEM((Cc, d), jnp.float32)],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Ec, Cc, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(counts, lle, x, w["wi_gate"], w["wi_up"], w["wo"])
