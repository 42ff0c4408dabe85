"""A whole run of a cell at a small size on the CPU, past the harness's look
for a chip: a sound run is correct; a token altered where it is produced,
and the control (the reference in fp8), are caught by the limit."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from benchlib import harness, spec  # noqa: E402

# a DeepSeek-MoE-shaped model: a dense first layer, then routed and shared
# experts; small enough for the Pallas kernels in interpret mode. One slot
# and one closed-loop client serve each request alone, so a request's
# tokens do not depend on how fast this machine runs.
TINY = {
    "model_type": "deepseek", "hidden_size": 128, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "n_routed_experts": 16, "n_shared_experts": 2,
    "num_experts_per_tok": 6, "norm_topk_prob": False, "vocab_size": 4096,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "arch": "deepseek-moe-16b",
    "deployment": {"max_slots": 1, "max_len": 128, "page_size": 16,
                   "kv_pool_bytes": 491520, "prefill_chunk": 32}}
MIX = {"loop": "closed", "clients_per_slot": 1, "pool": 64,
       "prompt": {"median": 30, "sigma": 0.5, "min": 8, "max": 90},
       "output": {"median": 24, "sigma": 0.5, "min": 8, "max": 36},
       "warmup": {"virtual_s": 3}}
# the limit of this small cell, from CPU readings over 4 seeds (PERF.md):
# sound runs at most 0.059, the fp8 control at least 0.128
LIMIT = 0.1
SEED = 2**33 + 1
SECONDS = 8.0
MIN_CHECKED = 60     # this cell's outputs are short


@pytest.fixture
def cell(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "cells"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "tiny-ds.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "chat.json").write_text(json.dumps(MIX))
    (bench / "cells" / "tiny.seq.json").write_text(
        json.dumps({"logit_gap_limit": LIMIT, "logit_gap_p99_limit": LIMIT}))
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    manifest["workloads"] = [{"name": "tiny.seq", "config": "tiny-ds",
                              "traffic": "chat", "chips": 1, "why": "test"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.seq"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return spec.load_cell("tiny.seq", bench_dir=bench)


@pytest.fixture
def few_tokens(monkeypatch):
    monkeypatch.setattr(harness, "MIN_CHECKED", MIN_CHECKED)


def test_sound_run_is_correct(cell, few_tokens):
    out = harness.run_cell(cell, SEED, SECONDS, False, time.monotonic())
    assert list(out)[-1] == "checks"
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["tokens_checked"]["value"] >= MIN_CHECKED


def test_altered_token_is_caught(cell, few_tokens, monkeypatch):
    """A token altered where the engine samples it."""
    import repro.serving.engine as engine
    sample = engine.sample

    def altered(logits, key, params):
        return (sample(logits, key, params) + 1) % TINY["vocab_size"]

    monkeypatch.setattr(engine, "sample", altered)
    out = harness.run_cell(cell, SEED, SECONDS, False, time.monotonic())
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > LIMIT


def test_fp8_control_is_caught(cell, few_tokens):
    """The reference in fp8, its first choices put in place of the served
    tokens, through the run's own check."""
    out = harness.run_cell(cell, SEED, SECONDS, False, time.monotonic(),
                           control="fp8")
    assert not out["correct"]
    assert out["checks"]["tokens_checked"]["value"] >= MIN_CHECKED
    assert out["checks"]["logit_gap"]["value"] > LIMIT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         json.loads((BENCH.parent / "BENCHMARK.json").read_text())
         ["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    r = _run(BENCH.parent)
    assert r.returncode != 0 and r.stdout == ""
    assert "TPU" in r.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0 and r.stdout == ""

