"""Launch-layer tests: input specs, rule building, microbatch heuristics,
roofline math, and one real dry-run cell in a subprocess (512 fake devices
must not leak into this process)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SHAPES, RunConfig
from repro.configs.registry import all_archs, get_config, get_shape
from repro.launch.roofline import RooflineTerms, model_bytes, model_flops
from repro.launch.specs import batch_specs, cache_axes, cell_input_specs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("arch", list(all_archs()))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_well_formed(arch, shape):
    from repro.configs.base import shape_applicable
    if not shape_applicable(arch, shape):
        pytest.skip("cell skipped by assignment rule")
    cfg = get_config(arch)
    sh = get_shape(shape)
    cell = cell_input_specs(cfg, sh)
    # batch tokens shaped per the shape spec
    b = cell["batch"]
    if sh.kind == "decode":
        assert b["tokens"].shape == (sh.global_batch, 1)
        assert "cache" in cell
        leaves = jax.tree_util.tree_leaves(cell["cache"])
        assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    elif cfg.is_encoder_decoder:
        assert b["frames"].shape[0] == sh.global_batch
        assert b["frames"].shape[1] == sh.seq_len // 2
    elif cfg.family == "vlm":
        assert b["tokens"].shape[1] + b["patch_embeds"].shape[1] == sh.seq_len
    else:
        assert b["tokens"].shape == (sh.global_batch, sh.seq_len)


def test_cache_axes_match_cache_structure(tiny_moe):
    from repro.models.model import abstract_cache
    ab = abstract_cache(tiny_moe, 2, 16)
    ax = cache_axes(tiny_moe)
    is_axes = lambda x: isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)
    jax.tree_util.tree_map(
        lambda a, s: None if len(a) == len(s.shape) else 1 / 0,
        ax, ab, is_leaf=is_axes)


def test_model_flops_scales():
    cfg = get_config("qwen3-8b")
    f_train = model_flops(cfg, get_shape("train_4k"))
    f_pref = model_flops(cfg, get_shape("prefill_32k"))
    # both ~1M tokens: train = 3x fwd(4k); prefill fwd(32k) has ~8x the
    # attention flops per token => ratio lands between 1.5 and 3
    assert 1.5 < f_train / f_pref < 3.0
    assert model_bytes(cfg, get_shape("decode_32k")) > 0


def test_roofline_terms_math():
    t = RooflineTerms(chips=256, flops_per_device=197e12,
                      bytes_per_device=819e9,
                      collective_bytes_per_device=50e9,
                      model_flops_global=197e12 * 128,
                      model_bytes_global=0.0)
    assert t.t_compute == pytest.approx(1.0)
    assert t.t_memory == pytest.approx(1.0)
    assert t.t_collective == pytest.approx(1.0)
    assert t.roofline_fraction == pytest.approx(0.5)
    assert t.dominant in ("compute", "memory", "collective")


def test_auto_num_micro_divides_batch():
    from repro.launch.mesh import make_production_mesh  # noqa: F401
    from repro.launch.steps import auto_num_micro

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    for arch in ("qwen3-8b", "mistral-large-123b", "olmoe-1b-7b"):
        cfg = get_config(arch)
        n = auto_num_micro(cfg, get_shape("train_4k"), FakeMesh,
                           RunConfig(seq_shard_activations=True))
        assert SHAPES["train_4k"].global_batch % n == 0


@pytest.mark.slow
def test_dryrun_subprocess_one_cell(tmp_path):
    """Real dry-run of the cheapest cell in a subprocess (the 512-device
    XLA flag must not contaminate this test process)."""
    out = str(tmp_path / "dr")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-2.7b",
         "--shape", "long_500k", "--mesh", "single", "--out", out],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.load(open(os.path.join(
        out, "mamba2-2.7b__long_500k__single.json")))
    assert rec["status"] == "ok"
    assert rec["roofline"]["t_bound"] > 0
    assert rec["mesh_info"]["num_devices"] == 256
    # this process still sees its own device world
    assert len(jax.devices()) < 256


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(8)).block_until_ready()
"""


def _cache_probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=compile_)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout.split()


def test_compile_cache_honours_env_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own reading of it stands, and
    compiled programs land there."""
    cache = tmp_path / "cache"
    used, configured = _cache_probe(cache, True)
    assert used == configured == str(cache)
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_checkout_dir():
    """Unset: the fixed, git-ignored directory at the checkout's root."""
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR
    used, configured = _cache_probe(None, False)
    assert used == configured == str(CHECKOUT_CACHE_DIR)
    assert str(CHECKOUT_CACHE_DIR.parent) == os.path.realpath(REPO)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
