"""Ragged scalar-prefetch MoE kernels: kernel-vs-reference parity under
skew/empty/boundary counts, cold-path empty-expert elision, count threading
through the duplex layer, engine-level token parity, capacity sizing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import MoEConfig, small_test_config
from repro.core.duplex_moe import default_capacities, moe_traffic_model
from repro.core.execution import ExecutionPlan, execution_plan, moe_execute
from repro.kernels import ops, ref
from repro.kernels.moe_gemm import moe_gemm_traffic
from repro.models.model import init_model
from repro.serving.engine import ServingEngine
from repro.serving.request import Request


def _case(seed, E, C, d, f, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((E, C, d)), dtype)
    w = {"wi_gate": jnp.asarray(rng.standard_normal((E, d, f)), dtype) * 0.1,
         "wi_up": jnp.asarray(rng.standard_normal((E, d, f)), dtype) * 0.1,
         "wo": jnp.asarray(rng.standard_normal((E, f, d)), dtype) * 0.1}
    return w, x


def _check(w, x, counts, **kw):
    cnt = jnp.asarray(counts, jnp.int32)
    out = ops.ragged_moe_gemm(w, x, cnt, interpret=True, **kw)
    exp = ref.ragged_moe_ffn_ref(w, x, cnt)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# ragged grouped GEMM vs reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("counts", [
    [32, 0, 0, 0, 0, 1],          # extreme skew: one full, one 1-token
    [0, 0, 5, 0, 32],             # expert 0 empty (lle edge case)
    [0, 0, 0, 0],                 # all experts empty
    [8, 16, 32, 24],              # counts exactly on block boundaries
    [7, 9, 31, 1, 17],            # counts straddling block boundaries
])
def test_ragged_gemm_count_patterns(counts):
    E = len(counts)
    w, x = _case(0, E, 32, 16, 64)
    _check(w, x, counts, c_block=8, f_block=32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_gemm_dtypes(dtype):
    w, x = _case(1, 4, 16, 32, 64, dtype)
    cnt = jnp.asarray([16, 3, 0, 9], jnp.int32)
    out = ops.ragged_moe_gemm(w, x, cnt, c_block=4, f_block=32,
                              interpret=True)
    exp = ref.ragged_moe_ffn_ref(w, x, cnt)
    tol = dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **tol)


def test_ragged_gemm_matches_padded_kernel_on_live_slots():
    """The ragged kernel must agree with the capacity-padded kernel wherever
    tokens are live — the slots the combine actually reads."""
    w, x = _case(2, 5, 24, 32, 64)
    counts = np.asarray([24, 0, 7, 13, 1])
    y_pad = ops.moe_gemm(w, x, c_block=8, f_block=32, interpret=True)
    y_rag = ops.ragged_moe_gemm(w, x, jnp.asarray(counts), c_block=8,
                                f_block=32, interpret=True)
    live = np.arange(24)[None, :] < counts[:, None]
    np.testing.assert_allclose(np.asarray(y_rag)[live],
                               np.asarray(y_pad)[live], atol=2e-5, rtol=2e-5)
    # and dead slots come back exactly zero (the ragged contract)
    assert float(np.abs(np.asarray(y_rag)[~live]).max()) == 0.0


def test_ragged_gemm_blocks_bound():
    """A trimmed token-block grid stays exact while every live block fits;
    counts past the bound are dropped (capacity semantics)."""
    w, x = _case(3, 4, 32, 16, 64)
    _check(w, x, [8, 2, 0, 15], c_block=8, f_block=32, blocks_bound=2)
    # bound drops tokens beyond blocks_bound * c_block
    cnt = jnp.asarray([32, 2, 0, 15], jnp.int32)
    out = ops.ragged_moe_gemm(w, x, cnt, c_block=8, f_block=32,
                              blocks_bound=2, interpret=True)
    exp = ref.ragged_moe_ffn_ref(w, x, jnp.minimum(cnt, 16))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_ragged_gemm_under_jit():
    w, x = _case(4, 3, 16, 16, 32)
    cnt = jnp.asarray([5, 0, 16], jnp.int32)
    f = jax.jit(lambda w, x, c: ops.ragged_moe_gemm(
        w, x, c, c_block=8, f_block=32, interpret=True))
    out = f(w, x, cnt)
    exp = ref.ragged_moe_ffn_ref(w, x, cnt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=12, deadline=None)
@given(data=st.data(), E=st.integers(1, 6))
def test_ragged_gemm_random_counts_property(data, E):
    """Parity must hold for ANY count vector (including clamping past C)."""
    C = 16
    counts = data.draw(st.lists(st.integers(0, C + 8),
                                min_size=E, max_size=E))
    w, x = _case(sum(counts) + 31 * E, E, C, 16, 32)
    _check(w, x, np.minimum(counts, C), c_block=4, f_block=32)


# ---------------------------------------------------------------------------
# ragged gather GEMV (cold path, empty-expert elision)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("counts", [[0, 0, 0], [4, 0, 2], [0, 3, 0],
                                    [4, 4, 4]])
def test_ragged_gemv_empty_expert_patterns(counts):
    E = len(counts)
    w, x = _case(5, E, 4, 32, 64)
    cnt = jnp.asarray(counts, jnp.int32)
    out = ops.moe_gemv(w, x, cnt, f_block=32, interpret=True)
    exp = ref.ragged_moe_ffn_ref(w, x, cnt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# in-place reads: the kernels address (layer, expert) rows of a stacked table
# ---------------------------------------------------------------------------

def _stacked_case(seed, L, E, C, d, f):
    """Tables of L layers of E experts flattened to (L*E, ., .), a slot
    buffer of E experts in rank order and a random rank permutation."""
    rng = np.random.default_rng(seed)
    w = {"wi_gate": rng.standard_normal((L * E, d, f)) * 0.1,
         "wi_up": rng.standard_normal((L * E, d, f)) * 0.1,
         "wo": rng.standard_normal((L * E, f, d)) * 0.1}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    x = jnp.asarray(rng.standard_normal((E, C, d)), jnp.float32)
    return w, x, rng.permutation(E).astype(np.int32)


@pytest.mark.parametrize("layer,f_block", [(1, 256), (2, 128)])
@pytest.mark.parametrize("kernel", ["ragged_gemm", "ragged_gemv",
                                    "padded_gemm", "padded_gemv"])
def test_kernels_read_stacked_table_rows(kernel, layer, f_block):
    """Each expert kernel reads its weights from rows ``layer*E + perm`` of
    a stacked (L*E, d, f) table, f = 384 (no 256-wide tile divides it:
    three of 128, no pad), and matches the reference on
    explicitly permuted weights. The id range (the hot ranks) starts with
    an empty expert and has one inside."""
    L, E, C, d, f, k_cold = 3, 8, 16, 32, 384, 2
    assert ops.expert_f_block(f, f_block) == 128
    w, x_all, perm = _stacked_case(10 + layer, L, E, C, d, f)
    rows = layer * E + perm[k_cold:]
    x = x_all[k_cold:]
    counts = jnp.asarray([0, 16, 5, 0, 9, 1], jnp.int32)
    w_perm = {k: v[rows] for k, v in w.items()}
    ids = jnp.asarray(rows)
    if kernel == "ragged_gemm":
        out = ops.ragged_moe_gemm(w, x, counts, ids=ids, c_block=8,
                                  f_block=f_block, interpret=True)
    elif kernel == "ragged_gemv":
        out = ops.moe_gemv(w, x, counts, ids=ids, f_block=f_block,
                           interpret=True)
    elif kernel == "padded_gemm":
        out = ops.moe_gemm(w, x, ids=ids, c_block=8, f_block=f_block,
                           interpret=True)
    else:
        out = ops.moe_gemv(w, x, ids=ids, f_block=f_block, interpret=True)
    exp = (ref.ragged_moe_ffn_ref(w_perm, x, counts)
           if kernel.startswith("ragged") else ref.moe_ffn_ref(w_perm, x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# duplex layer with count threading
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def duplex_setup():
    cfg = small_test_config(
        "rag-moe", family="moe", d_model=64,
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32))
    params = init_model(jax.random.PRNGKey(0), cfg)
    layer = jax.tree_util.tree_map(lambda a: a[0],
                                   params["segments"][0])["blocks"][0]["ffn"]
    return cfg, layer


@pytest.mark.parametrize("k_cold", [0, 2, 6])
def test_duplex_ragged_matches_padded(duplex_setup, k_cold):
    """The count-threaded kernels must not change the duplex layer output."""
    cfg, layer = duplex_setup
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, cfg.d_model))
    plans = [ExecutionPlan(moe_impl="duplex", k_cold=k_cold, c_hot=64,
                           c_cold=32, use_kernels=True, moe_ragged=ragged,
                           moe_c_block=8)
             for ragged in (False, True)]
    outs = []
    for plan in plans:
        with execution_plan(plan):
            y, _ = moe_execute(layer, cfg, x)
        outs.append(np.asarray(y))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# engine-level parity + accounting
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_setup():
    cfg = small_test_config(
        "rag-eng", family="moe", d_model=32,
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32))
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run_engine(cfg, params, *, ragged, use_kernels=True, layout="dense"):
    eng = ServingEngine(cfg, params, max_slots=4, max_len=32,
                        use_duplex=True, use_kernels=use_kernels,
                        moe_ragged=ragged, kv_layout=layout, kv_page_size=8)
    reqs = [Request(rid=i, prompt=list(range(1, 4 + i % 3)),
                    max_new_tokens=5) for i in range(6)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    return eng, {r.rid: tuple(r.output) for r in reqs}


def test_engine_ragged_matches_padded_tokens(engine_setup):
    """Greedy decode must emit identical tokens with the ragged kernels on,
    the padded kernels, and the XLA fallback."""
    cfg, params = engine_setup
    _, out_rag = _run_engine(cfg, params, ragged=True)
    _, out_pad = _run_engine(cfg, params, ragged=False)
    _, out_xla = _run_engine(cfg, params, ragged=False, use_kernels=False)
    assert out_rag == out_pad == out_xla


def test_engine_ragged_paged_matches_dense(engine_setup):
    """Ragged MoE + paged KV together (both scalar-prefetch paths active)."""
    cfg, params = engine_setup
    _, out_dense = _run_engine(cfg, params, ragged=True, layout="dense")
    _, out_paged = _run_engine(cfg, params, ragged=True, layout="paged")
    assert out_dense == out_paged


def test_engine_moe_accounting(engine_setup):
    cfg, params = engine_setup
    eng, _ = _run_engine(cfg, params, ragged=True)
    dec = [r for r in eng.reports if r.num_decode > 0]
    assert dec and all(r.moe_bytes_streamed > 0 for r in dec)
    # ragged executes at most the padded work, and the report's streamed
    # bytes reflect the ragged path (strictly below the padded model here)
    assert all(r.moe_flops_live <= r.moe_flops_padded for r in dec)
    eng_pad, _ = _run_engine(cfg, params, ragged=False)
    pad = [r for r in eng_pad.reports if r.num_decode > 0]
    assert (sum(r.moe_bytes_streamed for r in dec)
            < sum(r.moe_bytes_streamed for r in pad))


# ---------------------------------------------------------------------------
# capacity sizing (default_capacities k_cold regression) + traffic model
# ---------------------------------------------------------------------------

def test_default_capacities_uses_k_cold():
    """c_cold must be sized from the tail-rank expectation: monotone in
    k_cold, well below the mean for a small cold set, and ≈ the worst expert
    when every expert is cold."""
    m = MoEConfig(num_experts=64, top_k=2, d_ff_expert=32)
    T = 4096
    mean = T * m.top_k / m.num_experts
    cc = [default_capacities(T, m, k)[1] for k in (1, 8, 32, 64)]
    assert cc == sorted(cc)                      # monotone in k_cold
    assert cc[0] < cc[-1]                        # actually depends on k_cold
    assert cc[0] < mean                          # small tail ≪ uniform mean
    c_hot = default_capacities(T, m, 1)[0]
    assert cc[-1] <= 2 * c_hot                   # all-cold ≈ worst expert


def test_default_capacities_k_cold_zero_unchanged():
    m = MoEConfig(num_experts=8, top_k=2, d_ff_expert=32)
    c_hot, c_cold = default_capacities(64, m, 0)
    assert c_hot >= c_cold >= 1


def test_moe_gemm_traffic_scales_with_live_blocks():
    t = moe_gemm_traffic([64, 0, 8, 1], capacity=64, d_model=32, d_ff=64,
                         c_block=8)
    assert t["ragged_weight_bytes"] < t["padded_weight_bytes"]
    assert t["ragged_flops"] < t["padded_flops"]
    # live blocks: 8 + 0 + 1 + 1 = 10 of 4*8=32 padded blocks
    assert t["ragged_flops"] * 32 == t["padded_flops"] * 10
    # empty expert costs nothing
    t0 = moe_gemm_traffic([0, 0], capacity=16, d_model=8, d_ff=8, c_block=8)
    assert t0["ragged_flops"] == 0 and t0["ragged_bytes"] == 0


def test_moe_traffic_model_cold_path():
    stats = moe_traffic_model([0, 0, 3, 9, 20, 40], k_cold=3, c_hot=48,
                              c_cold=4, d_model=16, d_ff=32, c_block=8)
    # 2 of 3 cold experts empty: ragged cold weights = 1/3 of padded
    assert stats["ragged_weight_bytes"] < stats["padded_weight_bytes"]
    assert stats["ragged_flops"] <= stats["padded_flops"]


# ---------------------------------------------------------------------------
# benchmark smoke (the acceptance metric)
# ---------------------------------------------------------------------------

def test_moe_ragged_benchmark_reduction():
    import benchmarks.moe_ragged as bench
    rows = bench.run(quick=True)
    skewed = [r for r in rows if r["skew"] >= 2.0]
    assert skewed
    for r in skewed:
        assert r["reduction_bytes_x"] >= 2.0     # streamed weight bytes
        assert r["reduction_flops_x"] >= 2.0     # padded FLOPs
        assert r["reduction_x"] >= 2.0           # roofline time
    # ragged cost never exceeds padded anywhere in the sweep
    assert all(r["weight_bytes_ragged"] <= r["weight_bytes_padded"]
               and r["flops_ragged"] <= r["flops_padded"] for r in rows)
