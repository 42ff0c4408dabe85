"""Pallas TPU kernels for the paper's two execution paths + jnp oracles.

compute path (xPU analogue):    flash_attn.py, moe_gemm.py
bandwidth path (Logic-PIM):     decode_attn.py (dense + paged), moe_gemv.py
wrappers / oracles:             ops.py, ref.py
"""
import jax.numpy as jnp


def int8_quantize(x, *, keepdims: bool = False):
    """Canonical int8 abs-max quantization over the last axis: THE one
    recipe (abs-max / 127 steps, 1e-8 scale floor) shared by the
    model-layer KV cache (models/attention.py::quantize_kv) and the
    in-kernel q/pv requantization of the int8 paged kernels
    (decode_attn.py). Dense<->paged greedy-token parity depends on both
    paths quantizing bit-identically, so there is exactly one definition.
    Returns (int8 values, fp32 scale [``keepdims`` keeps the reduced
    axis])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                   keepdims=keepdims)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    div = scale if keepdims else scale[..., None]
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / div),
                 -127, 127).astype(jnp.int8)
    return q, scale


from repro.kernels.ops import (decode_attention, flash_attention, moe_gemm,
                               moe_gemv, paged_decode_attention,
                               ragged_moe_gemm)

__all__ = ["decode_attention", "flash_attention", "int8_quantize",
           "moe_gemm", "moe_gemv", "paged_decode_attention",
           "ragged_moe_gemm"]
