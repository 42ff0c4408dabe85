"""The expert kernels of a served stage read the MoE weight tables in place.

A paged mixed-stage program of a small DeepSeek-shaped model (a dense first
layer, then a 3-layer MoE segment, expert width 384) is traced with the
Pallas kernels in interpret mode. Its jaxpr must hold no copy of an expert
table outside the kernels: no rank-order gather, no hot/cold slice, no pad
and no per-layer slice of the stacked tables. The tables enter the layer
scan as constants, and each expert kernel takes two scalar-prefetch vectors
followed by rank-3 operands, which is how a device trace tells the expert
kernels apart.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (ATTN, DENSE, MOE, LayerKind, ModelConfig,
                                MoEConfig, Segment, small_test_config)
from repro.core.duplex_moe import duplex_moe_apply
from repro.core.execution import EXPERT_TABLES, moe_lowering_tally
from repro.models.model import init_model
from repro.serving.engine import ServingEngine

E, D, F, L = 8, 64, 384, 3
EXPERT_KERNELS = ("_ragged_moe_gemm_kernel", "_ragged_moe_gemv_kernel")


@pytest.fixture(scope="module")
def engine():
    cfg = ModelConfig(
        name="tiny-deepseek", family="moe", num_layers=1 + L, d_model=D,
        num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256,
        segments=(Segment((LayerKind(ATTN, DENSE),), 1),
                  Segment((LayerKind(ATTN, MOE),), L)),
        moe=MoEConfig(num_experts=E, top_k=2, d_ff_expert=F,
                      num_shared_experts=1, d_ff_shared=128,
                      norm_topk_probs=False),
        dtype="float32", param_dtype="float32").validate()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return ServingEngine(cfg, params, max_slots=4, max_len=64,
                         use_duplex=True, use_kernels=True, moe_ragged=True,
                         kv_layout="paged", kv_page_size=16)


def _mixed_jaxpr(eng, k_cold):
    """The jaxpr of the paged mixed stage of 4 decode rows and one 16-token
    chunk at ``k_cold``."""
    nb, mp, nc, sc, mpc = 4, 2, 1, 16, 2
    fn = eng._mixed_fn(k_cold, *eng._moe_caps(nb + nc * sc, k_cold),
                       nc, sc, nb, mp, mpc)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    return jax.make_jaxpr(fn)(eng.params, i32(nb, 1), i32(nb) + 1,
                              i32(nb, mp), i32(nc, sc), i32(nc),
                              i32(nc) + sc, i32(nc, mpc), eng.kv.cache,
                              jax.random.PRNGKey(0))


def _eqns(jaxpr, inside=()):
    """Every equation, with the primitives of the equations it sits in."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside + (eqn.primitive.name,))


def _is_table(aval):
    """An expert table, one layer's or the stack's, or a slice of one."""
    shape = getattr(aval, "shape", ())
    return len(shape) >= 3 and tuple(shape[-2:]) in ((D, F), (F, D))


@pytest.mark.parametrize("k_cold", [3, 0])
def test_no_expert_table_copy_outside_the_kernels(engine, k_cold):
    jaxpr = _mixed_jaxpr(engine, k_cold).jaxpr
    for eqn, inside in _eqns(jaxpr):
        if "pallas_call" in inside or eqn.primitive.name == "pallas_call":
            continue
        tables = [v.aval.shape for v in eqn.outvars if _is_table(v.aval)]
        if not tables:
            continue
        # the one table-shaped value made outside a kernel: the stack's
        # leading (L, E) dims merged for the kernels' row ids, a bitcast
        assert eqn.primitive.name == "reshape", (eqn.primitive, tables)
        (src,) = [v.aval.shape for v in eqn.invars]
        assert src[:2] == (L, E) and tables == [(L * E,) + src[2:]], eqn


@pytest.mark.parametrize("k_cold", [3, 0])
def test_expert_tables_are_scan_constants(engine, k_cold):
    jaxpr = _mixed_jaxpr(engine, k_cold).jaxpr
    scans = [e for e, _ in _eqns(jaxpr) if e.primitive.name == "scan"
             and any(_is_table(v.aval) for v in e.invars)]
    assert len(scans) == 1
    (scan,) = scans
    n = scan.params["num_consts"] + scan.params["num_carry"]
    consts = [v.aval.shape for v in scan.invars[:scan.params["num_consts"]]
              if _is_table(v.aval)]
    assert sorted(consts) == sorted([(L, E, D, F), (L, E, D, F),
                                     (L, E, F, D)])
    assert not any(_is_table(v.aval) for v in scan.invars[n:])


@pytest.mark.parametrize("k_cold", [3, 0])
def test_expert_kernels_take_two_prefetch_vectors(engine, k_cold):
    jaxpr = _mixed_jaxpr(engine, k_cold).jaxpr
    calls = {}
    for eqn, _ in _eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        name = eqn.params["jaxpr"].debug_info.func_name
        if name not in EXPERT_KERNELS:
            continue
        calls[name] = calls.get(name, 0) + 1
        n = eqn.params["grid_mapping"].num_index_operands
        assert n == 2, (name, n)
        assert all(v.aval.dtype == jnp.int32 and v.aval.ndim == 1
                   for v in eqn.invars[:n])
        assert [v.aval.ndim for v in eqn.invars[n:]] == [3, 3, 3, 3]
        assert [v.aval.shape[0] for v in eqn.invars[n + 1:]] == [L * E] * 3
    # one call of each flavour in the scan body (the cold GEMV only when
    # some experts are cold)
    assert calls == ({"_ragged_moe_gemm_kernel": 1,
                      "_ragged_moe_gemv_kernel": 1} if k_cold
                     else {"_ragged_moe_gemm_kernel": 1})


def test_stats_count_every_moe_layer_in_place(engine):
    for k_cold in (3, 0):
        _mixed_jaxpr(engine, k_cold)
    st = engine.stats()
    assert st["moe_gathered_layers"] == 0
    # two traced stage programs (k_cold 3 and 0), each of L MoE layers
    assert st["moe_inplace_layers"] == 2 * L


@pytest.mark.parametrize("f,kind", [(384, "inplace"), (640, "inplace"),
                                    (320, "gathered")])
def test_stacked_layer_matches_the_sliced_layer(f, kind):
    """A duplex MoE layer given the segment's stacked tables and a layer
    index matches the XLA path on that layer's own tables. 384 and 640 run
    in 128-wide tiles in place, while 320, which no 128-multiple divides,
    gathers the layer's tables (and pads them); the tally says which."""
    cfg = small_test_config("stack-moe", family="moe", d_model=32,
                            num_layers=L,
                            moe=MoEConfig(num_experts=E, top_k=2,
                                          d_ff_expert=f))
    stack = init_model(jax.random.PRNGKey(1), cfg)["segments"][0]
    stack = stack["blocks"][0]["ffn"]
    layer = 2
    one = jax.tree_util.tree_map(lambda a: a[layer], stack)
    # as the serving scan body sees it: this layer's router, every layer's
    # expert tables
    stacked = {**one, **{k: v for k, v in stack.items()
                         if k in EXPERT_TABLES}}
    x = jax.random.normal(jax.random.PRNGKey(2), (24, cfg.d_model))
    kw = dict(k_cold=3, c_hot=16, c_cold=8, c_block=8)
    with moe_lowering_tally() as tally:
        y, _ = duplex_moe_apply(stacked, cfg, x, use_kernels=True,
                                ragged=True, layer=jnp.int32(layer), **kw)
    y_ref, _ = duplex_moe_apply(one, cfg, x, **kw)
    assert dict(tally) == {kind: L}
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-5, rtol=2e-5)
