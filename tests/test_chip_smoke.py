"""chip_smoke.py: refuses to run off the chip, its phases hold at a tiny
size on the CPU (kernels in interpret mode), and its kernel-vs-XLA limits
catch a faulty attention kernel."""
import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import MoEConfig, small_test_config
from repro.models.model import init_model

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu(smoke, capsys):
    assert smoke.main() == 1
    out = capsys.readouterr()
    assert out.out == ""                   # no result line, nothing served
    assert "needs a TPU" in out.err


def test_config_keeps_published_widths(smoke):
    from repro.configs.registry import get_config
    full, cfg = get_config("olmoe-1b-7b"), smoke.smoke_config()
    assert cfg.num_layers == smoke.LAYERS < full.num_layers
    assert dataclasses.replace(cfg, num_layers=full.num_layers,
                               segments=full.segments) == full


@pytest.fixture(scope="module")
def tiny(smoke):
    # 16 MHA heads: the int8 kernel's two-group head blocking is exercised
    cfg = small_test_config(
        "smoke-tiny", family="moe", d_model=64, num_heads=16,
        num_kv_heads=16, head_dim=8, vocab_size=128, qk_norm=True,
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                      norm_topk_probs=False))
    cfg = dataclasses.replace(cfg, dtype="bfloat16", param_dtype="bfloat16")
    params = init_model(jax.random.PRNGKey(0), cfg)
    shape = smoke.SmokeShape(n_requests=3, prompt_len=20, max_new=3,
                             max_slots=4, max_len=32, page=8, chunk=16,
                             kv_budget=1 << 18, check_rows=2)
    return cfg, params, shape


@pytest.mark.parametrize("kv_quant", [False, True])
def test_phase_at_tiny_size(smoke, tiny, kv_quant):
    cfg, params, shape = tiny
    prompts = smoke.make_prompts(cfg, shape, 0)
    out = smoke.serve_phase(cfg, params, prompts, shape, kv_quant=kv_quant,
                            counter=smoke.CompileCounter(),
                            log=lambda *_: None)
    for run in out["runs"].values():
        assert run["tokens"] == shape.n_requests * shape.max_new
    assert set(out["check"]) == {"prefill", "decode"}
    for r in out["check"].values():
        assert smoke.relative_diff(r) <= smoke.LOGIT_TOL[
            "int8" if kv_quant else "bf16"]
    # the lowered stage programs were found among the served ones; in
    # interpret mode they hold no compiled kernel
    assert out["kernels"] and set(out["kernels"]) <= set(out["stage_k_cold"])
    assert all(names == [] for names in out["kernels"].values())


def _causal_mask_off_by_one(kernel):
    # every chunk query also sees the key one position past it
    def faulty(q, k, v, totals, starts, bt, **kw):
        return kernel(q, k, v, totals, starts + 1, bt, **kw)
    return faulty


def _last_page_dropped(kernel):
    # the page holding each sequence's newest key is left out
    def faulty(q, k, v, totals, starts, bt, **kw):
        page = k.shape[2]
        return kernel(q, k, v, jnp.maximum((totals - 1) // page * page, 0),
                      starts, bt, **kw)
    return faulty


def _kv_heads_rolled(kernel):
    # each kv head's output lands on its neighbour
    def faulty(*args, **kw):
        return jnp.roll(kernel(*args, **kw), 1, axis=1)
    return faulty


FAULTS = {f.__name__.lstrip("_"): f for f in
          (_causal_mask_off_by_one, _last_page_dropped, _kv_heads_rolled)}


@pytest.fixture
def fresh_traces():
    # the smoke's module-level jitted model calls must trace again with
    # (and after) a planted fault
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_kernel_fault_exceeds_limit(smoke, tiny, fresh_traces,
                                            monkeypatch, fault, kv_quant):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "paged_attention_kernel",
                        FAULTS[fault](ops.paged_attention_kernel))
    cfg, params, shape = tiny
    eng = smoke.make_engine(cfg, params, shape, kv_quant=kv_quant)
    rep = smoke.kernel_vs_xla(
        eng, smoke.make_prompts(cfg, shape, 0)[:shape.check_rows])
    worst = max(smoke.relative_diff(r) for r in rep.values())
    assert worst > smoke.LOGIT_TOL["int8" if kv_quant else "bf16"], rep


@pytest.mark.parametrize("kernels,ok", [
    ({0: ["_paged_kernel", "_ragged_moe_gemm_kernel"]}, True),
    ({48: ["_paged_kernel", "_ragged_moe_gemm_kernel",
           "_ragged_moe_gemv_kernel"]}, True),
    # every expert cold: no hot GEMM in the program
    ({64: ["_paged_kernel", "_ragged_moe_gemv_kernel"]}, True),
    ({48: ["_paged_kernel", "_ragged_moe_gemv_kernel"]}, False),
    ({0: ["_ragged_moe_gemm_kernel"]}, False),
    ({0: []}, False),
])
def test_check_kernels(smoke, kernels, ok):
    if ok:
        smoke.check_kernels("bf16", kernels, 64)
    else:
        with pytest.raises(smoke.SmokeCheckFailed):
            smoke.check_kernels("bf16", kernels, 64)
