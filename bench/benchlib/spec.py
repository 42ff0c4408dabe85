"""Where each piece of a cell lives, found by the names in BENCHMARK.json.

    bench/configs/<config>.json     model sizes and the deployment's sizes
    bench/traffic/<traffic>.json    parameters of one traffic mix
    bench/cells/<cell>.json         the cell's own numbers: rate or clients,
                                    and the limit of its correctness check
    bench/layer_metrics/<m>.py      the reader of per-layer metric <m>; a
                                    metric ``a.b`` is read by ``a.py``
    bench/peaks.json                the chip's peaks, keyed by device_kind

Adding a configuration, a traffic mix, a cell or a per-layer metric adds
files here and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]          # bench/configs/<config>.json
    traffic: Dict[str, Any]         # bench/traffic/<traffic>.json
    params: Dict[str, Any]          # bench/cells/<cell>.json
    end_to_end: List[dict]          # the cell's end-to-end metric entries
    per_layer: List[dict]           # the cell's per-layer metric entries


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: Path = BENCH_DIR,
              manifest: Optional[Path] = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json with its files."""
    spec = _read_json(manifest or bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=_read_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=_read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        params=_read_json(bench_dir / "cells" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def cell_from_files(name: str, config: str, traffic: str,
                    bench_dir: Path = BENCH_DIR) -> Cell:
    """A cell that BENCHMARK.json need not list (for the chip tools)."""
    params = bench_dir / "cells" / f"{name}.json"
    return Cell(name=name, chips=1, config_name=config, traffic_name=traffic,
                config=_read_json(bench_dir / "configs" / f"{config}.json"),
                traffic=_read_json(bench_dir / "traffic" / f"{traffic}.json"),
                params=_read_json(params) if params.is_file() else {},
                end_to_end=[], per_layer=[])


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The peak table's row for ``device_kind``; a chip not in the table is
    an error, never a default."""
    table = _read_json(bench_dir / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]


def metric_reader(metric: str,
                  bench_dir: Path = BENCH_DIR) -> Callable[[Any], Any]:
    """``read(ctx)`` of ``layer_metrics/<metric up to its first dot>.py``."""
    base = metric.split(".", 1)[0]
    path = bench_dir / "layer_metrics" / f"{base}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {metric!r}: "
                                f"{path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_layer_metric_{base}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
