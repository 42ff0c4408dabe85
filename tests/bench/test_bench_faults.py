"""A whole run at a small size on the CPU, past the harness's look for a
chip, with the timed path broken underneath: a stage step that returns
the KV cache it was given, unchanged, is caught by the check."""
import time

from test_bench_run import SECONDS, SEED, cell, few_tokens  # noqa: F401

from benchlib import harness


def test_unchanged_cache_is_caught(cell, few_tokens, monkeypatch):  # noqa: F811
    import repro.serving.engine as engine
    mixed, decode = engine.mixed_step, engine.decode_step

    def mixed_frozen(params, cfg, dec_tokens, chunk_tokens, cache, **kw):
        out = mixed(params, cfg, dec_tokens, chunk_tokens, cache, **kw)
        return out[:2] + (cache,) + out[3:]

    def decode_frozen(params, cfg, tokens, cache, **kw):
        out = decode(params, cfg, tokens, cache, **kw)
        return out[:1] + (cache,) + out[2:]

    monkeypatch.setattr(engine, "mixed_step", mixed_frozen)
    monkeypatch.setattr(engine, "decode_step", decode_frozen)
    out = harness.run_cell(cell, SEED, SECONDS, False, time.monotonic())
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        cell.params["logit_gap_limit"]
