"""The main-path Pallas kernels compile for a TPU v5e at OLMoE-1B-7B widths.

Interpret mode runs a kernel body as ordinary JAX, so it never sees what
the chip's compiler refuses: blocks that do not fit the (8, 128) tiling, or
more scoped VMEM than a kernel may take. Each case here lowers one kernel
through ``kernels/ops.py`` for a described (not attached) v5e chip and
compiles it — nothing runs. Widths are OLMoE-1B-7B's (d_model 2048, 16 heads
x 128, MHA, 64 experts of d_ff 1024) with the engine's page size and MoE
token block. The ``gqa*`` cases take grouped-query attention on 8 kv heads
x 128, where a 256-token chunk holds chunk x (query heads per kv head) query
rows per kv head: Command R's 64 query heads in bf16, Qwen3-8B's 32 in int8
(the int8 kernel steps over 8 kv heads at once, so its VMEM grows 8-fold).
The ``stacked*`` cases take DeepSeek-MoE-16B's expert width (1408, eleven
128-wide tiles, no pad) and read a 5-layer stack of its 64-expert tables in
place through row ids, as a served MoE layer does.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

HEADS, HEAD_DIM = 16, 128           # MHA: kv heads == heads
GQA_KV = 8
D_MODEL, EXPERTS, D_FF = 2048, 64, 1024
PAGE, POOL_PAGES, MAX_PAGES = 64, 512, 8
DECODE_ROWS, CHUNK_ROWS, CHUNK = 16, 4, 256
C_HOT, C_BLOCK, COLD, C_COLD = 256, 256, 32, 8
BF16, I32, I8, F32 = jnp.bfloat16, jnp.int32, jnp.int8, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _paged(quant, rows, n, starts=False, heads=HEADS, kv_heads=HEADS):
    kv = I8 if quant else BF16
    pool = (POOL_PAGES, kv_heads, PAGE, HEAD_DIM)
    args = [((n, rows, heads, HEAD_DIM), BF16), (pool, kv), (pool, kv),
            ((n,), I32)] + ([((n,), I32)] if starts else []) + [
            ((n, MAX_PAGES), I32)]
    if quant:
        args += [((POOL_PAGES, kv_heads, PAGE), F32)] * 2
    return args


def _experts(n):
    return {"wi_gate": ((n, D_MODEL, D_FF), BF16),
            "wi_up": ((n, D_MODEL, D_FF), BF16),
            "wo": ((n, D_FF, D_MODEL), BF16)}


STACK, DS_FF = 5 * EXPERTS, 1408


def _stacked():
    return {"wi_gate": ((STACK, D_MODEL, DS_FF), BF16),
            "wi_up": ((STACK, D_MODEL, DS_FF), BF16),
            "wo": ((STACK, DS_FF, D_MODEL), BF16)}


def _scaled(fn):
    """Call ``fn`` with the int8 scale pools (trailing) as keywords."""
    return lambda *a: fn(*a[:-2], k_scales=a[-2], v_scales=a[-1])


CASES = {
    "paged_decode_bf16": (
        lambda q, k, v, n, bt: ops.paged_decode_attention(
            q, k, v, n, bt, interpret=False),
        _paged(False, 1, DECODE_ROWS)),
    "paged_decode_int8": (
        _scaled(lambda q, k, v, n, bt, **kw: ops.paged_decode_attention(
            q, k, v, n, bt, interpret=False, **kw)),
        _paged(True, 1, DECODE_ROWS)),
    "chunked_prefill_bf16": (
        lambda q, k, v, t, s, bt: ops.chunked_prefill_attention(
            q, k, v, t, s, bt, interpret=False),
        _paged(False, CHUNK, CHUNK_ROWS, starts=True)),
    "chunked_prefill_int8": (
        _scaled(lambda q, k, v, t, s, bt, **kw: ops.chunked_prefill_attention(
            q, k, v, t, s, bt, interpret=False, **kw)),
        _paged(True, CHUNK, CHUNK_ROWS, starts=True)),
    "gqa8_chunked_prefill_bf16": (
        lambda q, k, v, t, s, bt: ops.chunked_prefill_attention(
            q, k, v, t, s, bt, interpret=False),
        _paged(False, CHUNK, CHUNK_ROWS, starts=True, heads=8 * GQA_KV,
               kv_heads=GQA_KV)),
    "gqa4_chunked_prefill_int8": (
        _scaled(lambda q, k, v, t, s, bt, **kw: ops.chunked_prefill_attention(
            q, k, v, t, s, bt, interpret=False, **kw)),
        _paged(True, CHUNK, CHUNK_ROWS, starts=True, heads=4 * GQA_KV,
               kv_heads=GQA_KV)),
    "dense_decode": (
        lambda q, k, v, n: ops.decode_attention(q, k, v, n, interpret=False),
        [((DECODE_ROWS, 1, HEADS, HEAD_DIM), BF16),
         ((DECODE_ROWS, MAX_PAGES * PAGE, HEADS, HEAD_DIM), BF16),
         ((DECODE_ROWS, MAX_PAGES * PAGE, HEADS, HEAD_DIM), BF16),
         ((DECODE_ROWS,), I32)]),
    "ragged_moe_gemm": (
        lambda w, x, c: ops.ragged_moe_gemm(w, x, c, c_block=C_BLOCK,
                                            interpret=False),
        [_experts(EXPERTS), ((EXPERTS, C_HOT, D_MODEL), BF16),
         ((EXPERTS,), I32)]),
    "padded_moe_gemm": (
        lambda w, x: ops.moe_gemm(w, x, c_block=C_BLOCK, interpret=False),
        [_experts(EXPERTS), ((EXPERTS, C_HOT, D_MODEL), BF16)]),
    "ragged_moe_gemv": (
        lambda w, x, c: ops.moe_gemv(w, x, c, interpret=False),
        [_experts(COLD), ((COLD, C_COLD, D_MODEL), BF16), ((COLD,), I32)]),
    "stacked_ragged_moe_gemm": (
        lambda w, x, c, ids: ops.ragged_moe_gemm(
            w, x, c, ids=ids, c_block=C_BLOCK, interpret=False),
        [_stacked(), ((EXPERTS - COLD, C_HOT, D_MODEL), BF16),
         ((EXPERTS - COLD,), I32), ((EXPERTS - COLD,), I32)]),
    "stacked_ragged_moe_gemv": (
        lambda w, x, c, ids: ops.moe_gemv(w, x, c, ids=ids, interpret=False),
        [_stacked(), ((COLD, C_COLD, D_MODEL), BF16), ((COLD,), I32),
         ((COLD,), I32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = CASES[name]
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(*s, sharding=one_chip), specs,
        is_leaf=lambda s: isinstance(s, tuple) and len(s) == 2
        and isinstance(s[0], tuple))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
