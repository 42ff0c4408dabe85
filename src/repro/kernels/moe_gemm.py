"""Pallas TPU grouped-expert GEMM kernels (the xPU-analogue MoE path).

Hot experts serve many tokens, so their FFN is compute-bound: the kernel
tiles (token-block × d_ff-block) MXU GEMMs per expert, fusing the SwiGLU
gate/up/activation/down chain so the (C, f) hidden activation never leaves
VMEM. Grid (E, nC, nF); the fp32 (bc, d) output accumulator is carried in
VMEM across the f-block dimension and written once.

Weight layout: tables of (R, d, f)/(R, f, d) rows, addressed through
``ids`` (E,) int32, a scalar-prefetch operand: grid step e reads table row
``ids[e]``. The tables are the parameters as they lie in HBM — a layer
stack flattened to (L*E, d, f) with ``ids = layer*E + perm[...]`` — so no
gather, slice or per-layer copy sits in front of the kernel. The expert dim
is the leading grid dim, so each expert's weights stream HBM->VMEM once per
token-block pass (weights re-read nC times; hot-path C is chosen so nC is 1
or 2).

Two variants:

  * ``moe_gemm_kernel`` — capacity-padded: runs the full (E, nC, nF) grid,
    so dead token blocks (slots past an expert's live token count) burn MXU
    flops *and* re-stream the expert's 3 weight matrices from HBM. Per-stage
    cost scales with the configured capacity, not the routed tokens — the
    MoE-side twin of the dense decode-attention pathology.

  * ``ragged_moe_gemm_kernel`` — per-expert live token counts ride in as a
    **scalar-prefetch** operand (``pltpu.PrefetchScalarGridSpec``). The x /
    weight / output index maps clamp dead (expert, token-block) grid steps to
    an already-resident block (the expert's last live block; for a fully
    empty expert, the last live block of the nearest preceding live expert),
    so Pallas elides their DMAs, and ``pl.when`` skips their compute —
    streamed weight bytes and FLOPs scale with *live* tokens per expert.
    Under continuous batching the per-expert counts fluctuate stage to stage
    (paper §III/§V-B); this kernel makes the executed cost track them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_gemm_kernel(ids_ref, x_ref, wg_ref, wu_ref, wo_ref, o_ref, acc_ref,
                     *, nf: int):
    del ids_ref                                      # read by the index maps
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                     # (bc, d)
    wg = wg_ref[0]                                   # (d, bf)
    wu = wu_ref[0]
    wo = wo_ref[0]                                   # (bf, d)
    g = jax.lax.dot(x, wg, preferred_element_type=jnp.float32)   # (bc, bf)
    u = jax.lax.dot(x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_ref[...] += jax.lax.dot(h, wo, preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _row_ids(ids, E: int):
    return (jnp.arange(E, dtype=jnp.int32) if ids is None
            else ids.astype(jnp.int32))


def moe_gemm_kernel(w, x, ids=None, *, c_block: int = 256,
                    f_block: int = 256, interpret: bool = False):
    """w: dict wi_gate/wi_up (R, d, f), wo (R, f, d) tables; x: (E, C, d);
    ids: (E,) int32 table row of each expert (default: row e). C % c_block
    == 0 and f % f_block == 0 (ops.py pads). -> (E, C, d)."""
    E, C, d = x.shape
    f = w["wi_gate"].shape[2]
    c_block = min(c_block, C)
    f_block = min(f_block, f)
    assert C % c_block == 0 and f % f_block == 0, (C, c_block, f, f_block)
    nc, nf = C // c_block, f // f_block

    kernel = functools.partial(_moe_gemm_kernel, nf=nf)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E, nc, nf),
        in_specs=[
            pl.BlockSpec((1, c_block, d), lambda e, ci, fi, ids: (e, ci, 0)),
            pl.BlockSpec((1, d, f_block),
                         lambda e, ci, fi, ids: (ids[e], 0, fi)),
            pl.BlockSpec((1, d, f_block),
                         lambda e, ci, fi, ids: (ids[e], 0, fi)),
            pl.BlockSpec((1, f_block, d),
                         lambda e, ci, fi, ids: (ids[e], fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, c_block, d),
                               lambda e, ci, fi, ids: (e, ci, 0)),
        scratch_shapes=[pltpu.VMEM((c_block, d), jnp.float32)],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, C, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(_row_ids(ids, E), x, w["wi_gate"], w["wi_up"], w["wo"])


# ---------------------------------------------------------------------------
# Ragged (count-aware, scalar-prefetch) grouped GEMM
# ---------------------------------------------------------------------------

def _ragged_moe_gemm_kernel(nb_ref, lle_ids_ref, x_ref, wg_ref, wu_ref, wo_ref,
                            o_ref, acc_ref, *, nf: int):
    e = pl.program_id(0)
    ci = pl.program_id(1)
    fi = pl.program_id(2)
    # dead (expert, token-block) steps skip all compute; their DMAs were
    # already elided by the clamped index maps.
    live = ci < nb_ref[e]

    @pl.when(live & (fi == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        x = x_ref[0]                                 # (bc, d)
        wg = wg_ref[0]                               # (d, bf)
        wu = wu_ref[0]
        wo = wo_ref[0]                               # (bf, d)
        g = jax.lax.dot(x, wg, preferred_element_type=jnp.float32)
        u = jax.lax.dot(x, wu, preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        acc_ref[...] += jax.lax.dot(h, wo, preferred_element_type=jnp.float32)

    @pl.when(live & (fi == nf - 1))
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _live_block_operands(counts, c_block: int, cap: int):
    """(nb, lle): per-expert live block counts and, for empty experts, the
    nearest preceding live expert whose resident blocks the index maps
    re-target (expert 0 if none)."""
    counts = jnp.minimum(counts.astype(jnp.int32), cap)
    nb = -(-counts // c_block)                       # ceil-div, 0 when empty
    E = counts.shape[0]
    idx = jnp.where(nb > 0, jnp.arange(E, dtype=jnp.int32), -1)
    lle = jnp.maximum(jax.lax.cummax(idx, axis=0), 0)
    return nb.astype(jnp.int32), lle.astype(jnp.int32)


def ragged_moe_gemm_kernel(w, x, counts, ids=None, *, c_block: int = 256,
                           f_block: int = 256,
                           blocks_bound: int | None = None,
                           interpret: bool = False):
    """w: dict wi_gate/wi_up (R, d, f), wo (R, f, d) tables; x: (E, C, d)
    slot buffers whose live tokens are a contiguous prefix of the C dim;
    counts: (E,) int32 live tokens per expert; ids: (E,) int32 table row of
    each expert (default: row e). C % c_block == 0 and f % f_block == 0
    (ops.py pads). -> (E, C, d).

    Two scalar-prefetch operands: ``nb`` (live blocks per expert) and
    ``concat(lle, ids)``. The clamp of dead steps stays in the slot
    buffers' expert order; the weight maps then look the table row up.

    The token-block grid extent is ``blocks_bound`` (defaults to C/c_block;
    the serving engine trims the grid by sizing C itself to a bucketed
    live-block count — ``blocks_bound`` is for callers holding a wider
    buffer). Tokens beyond blocks_bound*c_block are dropped (standard
    capacity-MoE semantics; the wrapper clamps ``counts`` to match).
    Slots at or past an expert's count come back **zeroed** (the wrapper
    masks them — dead blocks are never written by the kernel).
    """
    E, C, d = x.shape
    f = w["wi_gate"].shape[2]
    assert C % c_block == 0 and f % f_block == 0, (C, c_block, f, f_block)
    nc, nf = C // c_block, f // f_block
    nbound = nc if blocks_bound is None else blocks_bound
    assert 1 <= nbound <= nc, (nbound, nc)
    nb, lle = _live_block_operands(counts, c_block, nbound * c_block)
    lle_ids = jnp.concatenate([lle, _row_ids(ids, E)])

    kernel = functools.partial(_ragged_moe_gemm_kernel, nf=nf)

    def x_map(e, ci, fi, nb, lle_ids):
        # clamp dead steps to the expert's last live block (empty expert:
        # the nearest preceding live expert's last live block) — same block
        # index as the previous step, so the pipeline elides the DMA.
        del fi
        e_eff = jnp.where(nb[e] > 0, e, lle_ids[e])
        last = jnp.maximum(nb[e_eff] - 1, 0)
        return (e_eff, jnp.minimum(ci, last), 0)

    def wi_map(e, ci, fi, nb, lle_ids):
        # dead steps re-target the (e, nf-1) block left resident by the last
        # live step, so the 3 weight matrices are streamed once per *live*
        # token block only.
        e_eff = jnp.where(nb[e] > 0, e, lle_ids[e])
        fi_eff = jnp.where(ci < nb[e], fi, nf - 1)
        return (lle_ids[E + e_eff], 0, fi_eff)

    def wo_map(e, ci, fi, nb, lle_ids):
        e_eff = jnp.where(nb[e] > 0, e, lle_ids[e])
        fi_eff = jnp.where(ci < nb[e], fi, nf - 1)
        return (lle_ids[E + e_eff], fi_eff, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(E, nbound, nf),
        in_specs=[
            pl.BlockSpec((1, c_block, d), x_map),
            pl.BlockSpec((1, d, f_block), wi_map),
            pl.BlockSpec((1, d, f_block), wi_map),
            pl.BlockSpec((1, f_block, d), wo_map),
        ],
        out_specs=pl.BlockSpec((1, c_block, d), x_map),
        scratch_shapes=[pltpu.VMEM((c_block, d), jnp.float32)],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, C, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(nb, lle_ids, x, w["wi_gate"], w["wi_up"], w["wo"])


def moe_gemm_traffic(counts, *, capacity: int, d_model: int, d_ff: int,
                     c_block: int, itemsize: int = 2, mats: int = 3):
    """Modeled per-layer HBM traffic + FLOPs of the hot-expert grouped GEMM,
    padded vs ragged (DMA-elision semantics of ragged_moe_gemm_kernel).

    Each executed token block streams the expert's ``mats`` weight matrices
    (d×f) once and moves c_block×d of activations in and out; padded runs
    every (expert, block), ragged only the live ones. Returns a dict with
    ``{padded,ragged}_{bytes,weight_bytes,flops}``.
    """
    import numpy as np
    counts = np.minimum(np.asarray(counts, dtype=np.int64), capacity)
    E = len(counts)
    cb = min(c_block, capacity)
    nc = -(-capacity // cb)
    nb_live = -(-counts // cb)                       # live blocks per expert
    w_block = mats * d_model * d_ff * itemsize       # weights per token block
    a_block = 2 * cb * d_model * itemsize            # x in + y out per block
    flops_block = 2 * mats * cb * d_model * d_ff
    padded_blocks = E * nc
    ragged_blocks = int(nb_live.sum())
    return {
        "padded_weight_bytes": padded_blocks * w_block,
        "ragged_weight_bytes": ragged_blocks * w_block,
        "padded_bytes": padded_blocks * (w_block + a_block),
        "ragged_bytes": ragged_blocks * (w_block + a_block),
        "padded_flops": padded_blocks * flops_block,
        "ragged_flops": ragged_blocks * flops_block,
    }
