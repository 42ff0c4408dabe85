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

Both kernels read the weight tables in place: grid step e reads table row
``ids[e]`` (a scalar-prefetch operand), as in ``moe_gemm.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.moe_gemm import _row_ids


def _moe_gemv_kernel(ids_ref, x_ref, wg_ref, wu_ref, wo_ref, o_ref, acc_ref,
                     *, nf: int):
    del ids_ref                                      # read by the index maps
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


def moe_gemv_kernel(w, x, ids=None, *, f_block: int = 256,
                    interpret: bool = False):
    """w: dict wi_gate/wi_up (R, d, f), wo (R, f, d) tables; x: (Ec, Cc, d)
    with a small Cc; ids: (Ec,) int32 table row of each expert (default:
    row e). f % f_block == 0 (ops.py pads). -> (Ec, Cc, d)."""
    Ec, Cc, d = x.shape
    f = w["wi_gate"].shape[2]
    f_block = min(f_block, f)
    assert f % f_block == 0, (f, f_block)
    nf = f // f_block

    kernel = functools.partial(_moe_gemv_kernel, nf=nf)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Ec, nf),
        in_specs=[
            pl.BlockSpec((1, Cc, d), lambda e, fi, ids: (e, 0, 0)),
            pl.BlockSpec((1, d, f_block), lambda e, fi, ids: (ids[e], 0, fi)),
            pl.BlockSpec((1, d, f_block), lambda e, fi, ids: (ids[e], 0, fi)),
            pl.BlockSpec((1, f_block, d), lambda e, fi, ids: (ids[e], fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, Cc, d), lambda e, fi, ids: (e, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Cc, d), jnp.float32)],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Ec, Cc, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_row_ids(ids, Ec), x, w["wi_gate"], w["wi_up"], w["wo"])


# ---------------------------------------------------------------------------
# Ragged (count-aware, scalar-prefetch) gather GEMV
# ---------------------------------------------------------------------------

def _ragged_moe_gemv_kernel(cnt_ref, lle_ids_ref, x_ref, wg_ref, wu_ref, wo_ref,
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


def ragged_moe_gemv_kernel(w, x, counts, ids=None, *, f_block: int = 256,
                           interpret: bool = False):
    """Like ``moe_gemv_kernel`` but empty experts (counts[e] == 0) stream no
    weights: the index maps clamp them to the nearest preceding occupied
    expert's resident blocks (DMA elided) and compute is skipped. counts:
    (Ec,) int32. Two scalar-prefetch operands: ``counts`` and
    ``concat(lle, ids)``; the clamp stays in the slot buffers' expert order
    and the weight maps then look the table row up. Empty experts' output
    rows come back zeroed via the ops.py wrapper mask. -> (Ec, Cc, d)."""
    Ec, Cc, d = x.shape
    f = w["wi_gate"].shape[2]
    f_block = min(f_block, f)
    assert f % f_block == 0, (f, f_block)
    nf = f // f_block
    counts = counts.astype(jnp.int32)
    idx = jnp.where(counts > 0, jnp.arange(Ec, dtype=jnp.int32), -1)
    lle = jnp.maximum(jax.lax.cummax(idx, axis=0), 0).astype(jnp.int32)
    lle_ids = jnp.concatenate([lle, _row_ids(ids, Ec)])

    kernel = functools.partial(_ragged_moe_gemv_kernel, nf=nf)

    def x_map(e, fi, cnt, lle_ids):
        del fi
        return (jnp.where(cnt[e] > 0, e, lle_ids[e]), 0, 0)

    def wi_map(e, fi, cnt, lle_ids):
        live = cnt[e] > 0
        row = lle_ids[Ec + jnp.where(live, e, lle_ids[e])]
        return (row, 0, jnp.where(live, fi, nf - 1))

    def wo_map(e, fi, cnt, lle_ids):
        live = cnt[e] > 0
        row = lle_ids[Ec + jnp.where(live, e, lle_ids[e])]
        return (row, jnp.where(live, fi, nf - 1), 0)

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
    )(counts, lle_ids, x, w["wi_gate"], w["wi_up"], w["wo"])
