#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload deepseek-moe.batch-1k --seed 7 --seconds 51 --trace 0

The cell (BENCHMARK.json ``workloads``) names a configuration and a traffic
mix; ``bench/benchlib/spec.py`` says where their files are. The run draws
the weights and the traffic from ``--seed``, warms up, measures for
``--seconds``, drains the requests due in the window, checks a sample of
them against the plain reference, and prints one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared, with its limit.

It exits nonzero and prints no result where JAX finds no TPU, or fewer
chips than the cell asks for. JAX's persistent compilation cache lives in
``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".jax_cache"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", metavar="DIR",
                   help="write the profiler trace of a --trace 1 run here "
                        "instead of a temporary directory")
    args = p.parse_args(argv)

    # the cache directory is fixed and inside the checkout, whatever the
    # environment says: the two sides of a comparison share nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    from benchlib import harness, spec

    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    trace_dir = None
    if args.trace:
        trace_dir = args.keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS, trace_dir)
    finally:
        if trace_dir and not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
