"""Pallas TPU kernel: Mamba-2 SSD single-token state update (decode).

The attention-free archs' decode step is a recurrence over the SSM state
(B, H, N, P): read the state, decay it, add the rank-1 update, contract
with C — ~2 Op/B, exactly the band the paper routes to Logic-PIM
(DESIGN.md §4 Arch-applicability: C1 sends mamba_decode to the bandwidth
path). The kernel streams the fp32 state HBM->VMEM->HBM exactly once per
step with the per-head block resident in VMEM.

Grid (B, H/hb). Inputs per block: state (1, hb, N, P) fp32, x (1, hb, P),
dt (1, hb), A (hb,), Bv/Cv (1, N), D (hb,). Outputs: y (1, hb, P) and the
new state. Validated in interpret mode against ``ref.ssd_decode_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_decode_kernel(state_ref, x_ref, dt_ref, a_log_ref, b_ref, c_ref,
                       d_ref, y_ref, new_state_ref):
    state = state_ref[0]                              # (hb, N, P) fp32
    x = x_ref[0].astype(jnp.float32)                  # (hb, P)
    dt = dt_ref[0].astype(jnp.float32)                # (hb,)
    a_log = a_log_ref[...].astype(jnp.float32)        # (hb,)
    bv = b_ref[0].astype(jnp.float32)                 # (N,)
    cv = c_ref[0].astype(jnp.float32)                 # (N,)
    dres = d_ref[...].astype(jnp.float32)             # (hb,)

    decay = jnp.exp(dt * (-jnp.exp(a_log)))           # (hb,)
    upd = (dt[:, None, None] * bv[None, :, None] * x[:, None, :])
    new_state = state * decay[:, None, None] + upd    # (hb, N, P)
    y = jnp.einsum("n,hnp->hp", cv, new_state,
                   preferred_element_type=jnp.float32)
    y = y + dres[:, None] * x
    new_state_ref[0] = new_state
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_decode_kernel(state, x, dt, a_log, b, c, d, *, h_block: int = 8,
                      interpret: bool = False):
    """state: (B, H, N, P) fp32; x: (B, H, P); dt: (B, H); a_log, d: (H,);
    b, c: (B, N). Returns (y (B, H, P), new_state). H % h_block == 0."""
    B, H, N, P = state.shape
    h_block = min(h_block, H)
    assert H % h_block == 0, (H, h_block)

    return pl.pallas_call(
        _ssd_decode_kernel,
        grid=(B, H // h_block),
        in_specs=[
            pl.BlockSpec((1, h_block, N, P), lambda b_, h: (b_, h, 0, 0)),
            pl.BlockSpec((1, h_block, P), lambda b_, h: (b_, h, 0)),
            pl.BlockSpec((1, h_block), lambda b_, h: (b_, h)),
            pl.BlockSpec((h_block,), lambda b_, h: (h,)),
            pl.BlockSpec((1, N), lambda b_, h: (b_, 0)),
            pl.BlockSpec((1, N), lambda b_, h: (b_, 0)),
            pl.BlockSpec((h_block,), lambda b_, h: (h,)),
        ],
        out_specs=[
            pl.BlockSpec((1, h_block, P), lambda b_, h: (b_, h, 0)),
            pl.BlockSpec((1, h_block, N, P), lambda b_, h: (b_, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(state, x, dt, a_log, b, c, d)
