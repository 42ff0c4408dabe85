"""The benchmark's traffic generator and the lookup of its data files."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402
from benchlib.loadgen import Traffic, lognormal_lengths  # noqa: E402

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
CODE = json.loads((BENCH / "traffic" / "code-batch.json").read_text())
BATCH = json.loads((BENCH / "traffic" / "batch-1k.json").read_text())


def _lengths(block):
    return (sorted(a.prompt.size for a in block),
            sorted(a.max_new for a in block))


@pytest.mark.parametrize("mix,kw", [(CHAT, {"rate": 1.5}),
                                    (CODE, {"max_slots": 32}),
                                    (BATCH, {"max_slots": 32})],
                         ids=["chat", "code-batch", "batch-1k"])
def test_same_seed_same_requests(mix, kw):
    a = Traffic(mix, 2**33 + 17, 50304, 51, **kw).block(0)
    b = Traffic(mix, 2**33 + 17, 50304, 51, **kw).block(0)
    assert [x.due for x in a] == [x.due for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.max_new for x in a] == [x.max_new for x in b]


@pytest.mark.parametrize("mix,kw", [(CHAT, {"rate": 1.5}),
                                    (CODE, {"max_slots": 32})],
                         ids=["chat", "code-batch"])
def test_seeds_reorder_the_same_work(mix, kw):
    a = Traffic(mix, 1, 50304, 51, **kw).block(0)
    b = Traffic(mix, 2, 50304, 51, **kw).block(0)
    assert _lengths(a) == _lengths(b)
    assert [x.prompt.size for x in a] != [x.prompt.size for x in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("mix", [CHAT, CODE, BATCH],
                         ids=["chat", "code-batch", "batch-1k"])
def test_lengths_follow_the_distribution(mix):
    for part in ("prompt", "output"):
        dist = mix[part]
        x = lognormal_lengths(dist, 1001)
        assert x.min() >= dist["min"] and x.max() <= dist["max"]
        assert np.median(x) == pytest.approx(dist["median"], rel=0.01)
        # the quartiles lie inside the clips; a normal's interquartile
        # range is 1.349 sigma (rounding to whole tokens moves it a little)
        q75, q25 = np.percentile(np.log(x), [75, 25])
        assert (q75 - q25) / 1.349 == pytest.approx(dist["sigma"], rel=0.1)


def test_open_loop_rate_and_span():
    t = Traffic(CHAT, 9, 50304, 51, rate=1.5)
    block = t.block(0)
    assert len(block) == round(1.5 * 51)
    dues = [a.due for a in block]
    assert dues[0] == 0.0 and dues == sorted(dues) and dues[-1] < 51
    assert all(0 <= tok < 50304 for a in block for tok in a.prompt[:16])


def test_closed_loop_clients():
    t = Traffic(CODE, 9, 50304, 51, max_slots=32)
    assert t.clients == 64
    assert len(t.block(0)) == CODE["pool"]
    with pytest.raises(ValueError):
        Traffic(CHAT, 9, 50304, 51)          # open loop without a rate


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    """A later PR adds a mix and a cell as data files, and edits none."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    mix = dict(CHAT, prompt={"median": 200, "sigma": 0.3, "min": 16,
                             "max": 512})
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (bench / "cells" / "dummy.cell.json").write_text(
        json.dumps({"rate_per_s": 3.0}))
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    config = manifest["configs"][0]["name"]
    manifest["workloads"].append({"name": "dummy.cell", "config": config,
                                  "traffic": "dummy-mix", "chips": 1,
                                  "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell = spec.load_cell("dummy.cell", bench_dir=bench)
    assert cell.traffic["prompt"]["median"] == 200
    assert cell.params["rate_per_s"] == 3.0
    block = Traffic(cell.traffic, 1, 100, 10,
                    rate=cell.params["rate_per_s"]).block(0)
    assert len(block) == 30
    assert np.median([a.prompt.size for a in block]) == pytest.approx(
        200, rel=0.05)
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell", bench_dir=bench)


def test_each_benchmark_name_has_its_files():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in manifest["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["deployment"]["max_len"] > 0
    for m in manifest["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")
