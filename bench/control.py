#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from, on the chip.

For each seed, in one process: the cell's traffic at its own load for a
window of ``--seconds``; then the reference over the same sample of
finished requests that a benchmark run compares. Each row gives, through
the run's own check (``harness.judge``, with the cell's limits), the
program's readings and verdict and, in the program's place, each
control's: the reference computed in int8 or fp8, whose first choice at
each served position is compared. A control has to come out not correct.

    python3 bench/control.py --cell deepseek-moe.batch-1k \
        --config deepseek-moe-16b-d6 --traffic batch-1k --seconds 30 \
        --seeds 101 102 103 --controls fp8 --out readings.jsonl
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--cell", required=True)
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--controls", nargs="*", default=["fp8"])
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    from benchlib import harness, reference, spec
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    cell = spec.cell_from_files(args.cell, args.config, args.traffic)
    ses = harness.Session(cell, args.seeds[0], args.seconds)
    ses.warm_up()
    with open(args.out, "a") as out:
        for i, seed in enumerate(args.seeds):
            if i:
                ses.set_seed(seed)
                ses.warm_up()
            win = ses.window()
            seqs, oov = ses.finished_sample(win)
            ses.drv.cancel_all()
            ses.eng.params = ses.params = None
            gc.collect()
            t0 = time.monotonic()
            got = reference.compare(seed, ses.dims, seqs, args.controls)
            row = {"cell": args.cell, "seed": seed, "tokens": got["tokens"],
                   "requests": len(seqs), "window_programs": win.programs}
            for name in ("program",) + tuple(args.controls):
                checks, correct = harness.judge(
                    got["gaps" if name == "program" else name], oov,
                    cell.params)
                row[name] = {"correct": correct,
                             **{k: c["value"] for k, c in checks.items()}}
            row["reference_s"] = time.monotonic() - t0
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
