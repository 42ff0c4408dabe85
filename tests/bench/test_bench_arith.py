"""The benchmark's own operation and byte counts, and the configurations'
sizes, against counts made by hand at the published widths."""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from benchlib import arith, harness, weights  # noqa: E402
from benchlib.model import dims_of  # noqa: E402


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


OLMOE = dims_of(_config("olmoe-1b-7b-d8"))
DEEPSEEK = dims_of(_config("deepseek-moe-16b-d6"))


def test_attention_decode_row_by_hand():
    # one token over 1000 positions, 16 heads x 128, 8 layers:
    # QK^T and PV are 2 * 16 * 128 * 1000 FLOPs each
    f, b = arith.attn_work(OLMOE, [("decode", 1000)])
    assert f == 8 * 4 * 16 * 128 * 1000
    # K and V of 1000 positions, 16 kv heads x 128, bf16; q in and out
    assert b == 8 * 2 * (2 * 1000 * 16 * 128 + 2 * 16 * 128)


def test_attention_chunk_by_hand():
    # prompt positions [256, 512): query i sees i + 1 keys
    f, b = arith.attn_work(OLMOE, [("chunk", 256, 512, False, 0)])
    pairs = sum(i + 1 for i in range(256, 512))
    assert pairs == 98432
    assert f == 8 * 4 * 16 * 128 * pairs
    assert b == 8 * 2 * (2 * 512 * 16 * 128 + 2 * 256 * 16 * 128)


def test_moe_by_hand():
    f, b = arith.moe_work(OLMOE, 32)
    # 32 tokens x top-8 experts x SwiGLU (3 matmuls of 2048 x 1024)
    assert f == 8 * 32 * 8 * 3 * 2 * 2048 * 1024
    touched = 64 * (1 - (7 / 8) ** 32)
    assert touched == pytest.approx(63.108, abs=1e-3)
    assert b == pytest.approx(8 * 2 * (touched * 3 * 2048 * 1024
                                       + 2 * 8 * 32 * 2048))
    # DeepSeek: 5 MoE layers, top-6 of 64 experts of width 1408
    f, _ = arith.moe_work(DEEPSEEK, 100)
    assert f == 5 * 100 * 6 * 3 * 2 * 2048 * 1408


def test_model_flops_per_token():
    body, head = arith.active_params(OLMOE)
    # attention 4 x 2048 x 2048 and 8 experts of 3 x 2048 x 1024 plus the
    # router, per layer; 8 layers; the head 50304 x 2048
    assert body == 8 * (4 * 2048 * 2048 + 8 * 3 * 2048 * 1024 + 2048 * 64)
    assert head == 50304 * 2048
    # about 1.28 GFLOP per decoded token at short context
    assert 2 * (body + head) == pytest.approx(1.28e9, rel=0.01)
    rows = [("decode", 10), ("chunk", 0, 256, True, 1),
            ("chunk", 0, 256, False, 2)]
    attn, _ = arith.attn_work(OLMOE, rows)
    assert arith.model_flops(OLMOE, rows) == 2 * body * 513 + 2 * head * 2 \
        + attn


@pytest.mark.parametrize("dims,params", [
    # 8 layers: attention 4 x 2048^2, 64 experts of 3 x 2048 x 1024, router
    (OLMOE, 8 * (4 * 2048 ** 2 + 64 * 3 * 2048 * 1024 + 2048 * 64)
     + 2 * 50304 * 2048),
    # dense layer of width 10944, 5 MoE layers of 64 x 1408 experts plus
    # 2 shared (2816 wide), router
    (DEEPSEEK, 6 * 4 * 2048 ** 2 + 3 * 2048 * 10944
     + 5 * (64 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64)
     + 2 * 102400 * 2048),
], ids=["olmoe-1b-7b-d8", "deepseek-moe-16b-d6"])
def test_config_gives_the_cut_parameter_bytes(dims, params):
    drawn = sum(int(np.prod(shape)) for i in range(dims.layers)
                for shape, _ in weights.layer_shapes(
                    dims, dims.kind(i)).values())
    drawn += 2 * dims.vocab * dims.hidden
    assert drawn == params
    assert 2 * params == pytest.approx(7.1e9 if dims.family == "olmoe"
                                       else 6.9e9, rel=0.02)


@pytest.mark.parametrize("name", ["olmoe-1b-7b-d8", "deepseek-moe-16b-d6"])
def test_program_takes_the_drawn_tree(name):
    """The weights the benchmark draws fill the program's parameter tree
    leaf for leaf, at the configuration's sizes."""
    from repro.models.model import abstract_model
    cfg = _config(name)
    dims = dims_of(cfg)
    prog = harness.program_config(cfg, dims)
    want = abstract_model(prog)
    got = jax.eval_shape(lambda: weights.program_params(0, dims))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert prog.num_layers == dims.layers
    assert prog.norm_eps == dims.eps
