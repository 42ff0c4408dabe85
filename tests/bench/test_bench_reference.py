"""The plain reference: its packing, and its agreement with an independent
float32 forward pass at a small size."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from benchlib import harness, reference, weights  # noqa: E402
from benchlib.model import dims_of  # noqa: E402

TINY_DEEPSEEK = {
    "model_type": "deepseek", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "n_routed_experts": 8, "n_shared_experts": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": False, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "arch": "deepseek-moe-16b"}


def test_pack_reads_each_served_token_after_its_prefix():
    toks, seg, pos, rows, targets, n = reference.pack(
        [([5, 6, 7], [8, 9]), ([1, 2], [3, 4, 5])])
    assert n == 5
    assert toks[:8].tolist() == [5, 6, 7, 8, 1, 2, 3, 4]
    assert seg[:8].tolist() == [0, 0, 0, 0, 1, 1, 1, 1]
    assert pos[:8].tolist() == [0, 1, 2, 3, 0, 1, 2, 3]
    # served token 8 is read at position 2 (after 5 6 7), 9 after 8 ...
    assert rows[:5].tolist() == [2, 3, 5, 6, 7]
    assert targets[:5].tolist() == [8, 9, 3, 4, 5]
    assert len(toks) % reference.PACK == 0
    assert len(rows) % reference.READ_BLOCK == 0


def test_weights_drawn_again_are_the_served_ones():
    dims = dims_of(TINY_DEEPSEEK)
    key = weights.seed_key(2**33 + 3)
    stacked = weights.draw_layers(key, dims, "moe", [1, 2])
    again = weights.draw_layer(key, dims, "moe", 2)
    for name, w in again.items():
        assert np.array_equal(np.asarray(stacked[name][1]), np.asarray(w))
        assert w.dtype == jnp.bfloat16


def test_reference_matches_an_independent_forward():
    """At float32 the program's own XLA forward pass (no cache, no kernels,
    no expert capacity limit) and the reference give the same gaps."""
    from repro.models.model import forward
    dims = dims_of(TINY_DEEPSEEK)
    prog = harness.program_config(TINY_DEEPSEEK, dims)
    prog = dataclasses.replace(
        prog, dtype="float32", param_dtype="float32",
        moe=dataclasses.replace(prog.moe, capacity_factor=16.0)).validate()
    seed = 4321
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    weights.program_params(seed, dims))
    rng = np.random.default_rng(0)
    seqs = [(rng.integers(0, 256, 20).tolist(), rng.integers(0, 256, 6)
             .tolist()), (rng.integers(0, 256, 33).tolist(),
                          rng.integers(0, 256, 4).tolist())]
    got = reference.compare(seed, dims, seqs)
    want = []
    with jax.default_matmul_precision("highest"):
        for p, o in seqs:
            lg = np.asarray(forward(params, prog, {"tokens": jnp.asarray(
                [p + o[:-1]], jnp.int32)})[0][0])
            at = lg[np.arange(len(p) - 1, len(p) - 1 + len(o))]
            want.extend(at.max(-1) - at[np.arange(len(o)), o])
    assert got["tokens"] == 10
    np.testing.assert_allclose(got["gaps"], want, atol=1e-4)


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_a_control_rounds_its_operands(quant):
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    exact = reference.mm(a, w, None)
    low = reference.mm(a, w, quant)
    err = float(jnp.abs(low - exact).max() / jnp.abs(exact).max())
    assert 1e-4 < err < 0.1


def test_olmoe_norms_q_and_k_over_the_whole_projection():
    """The published OLMoE applies RMSNorm to the whole q (and k)
    projection before it splits into heads. Scaling one head's q weights
    would leave a per-head norm's output as it was; under the whole-
    projection norm it changes the attention output."""
    cfg = dict(TINY_DEEPSEEK, model_type="olmoe", intermediate_size=32,
               num_experts=8, num_key_value_heads=4)
    dims = dims_of(cfg)
    assert dims.qk_norm
    w = weights.draw_layer(weights.seed_key(1), dims, "moe", 0)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(reference.Q_BLOCK,
                                                          64)), jnp.float32)
    seg = jnp.zeros((reference.Q_BLOCK,), jnp.int32)
    pos = jnp.arange(reference.Q_BLOCK, dtype=jnp.int32)
    w2 = dict(w, q=w["q"].at[:, :16].multiply(3.0))
    base = reference.attention(x, w, seg, pos, dims, None)
    scaled = reference.attention(x, w2, seg, pos, dims, None)
    assert float(jnp.abs(base - scaled).max()) > 1e-3
