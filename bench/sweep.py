#!/usr/bin/env python3
"""Find the rate an open-loop cell sustains: one process, one warm-up, then
for each rate a fill pass and a measured window (no drain). A rate holds
when the requests due late in the window wait no longer for their first
token than those due early, and few are still waiting at its end.

    python3 bench/sweep.py --cell olmoe.chat --config olmoe-1b-7b-d8 \\
        --traffic chat --seed 5 --seconds 40 --rates 0.6 0.8 1.0 1.2
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--cell", required=True)
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--fill", type=float, default=20.0)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    from benchlib import harness, spec
    from benchlib.loadgen import Traffic
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    cell = spec.cell_from_files(args.cell, args.config, args.traffic)
    ses = harness.Session(cell, args.seed, args.seconds, rate=args.rates[0])
    ses.warm_up()
    drv = ses.drv
    for i, rate in enumerate(args.rates):
        drv.cancel_all()
        ses.traffic = Traffic(cell.traffic, args.seed, ses.dims.vocab,
                              args.seconds, rate=rate,
                              max_slots=ses.dep["max_slots"])
        base = drv.clock()
        fill = harness.OpenSource(ses.traffic, 2000 + i, base)
        harness.drive(drv, fill, lambda now: now >= base + args.fill,
                      lambda k: "warm")
        n0 = len(drv.sent)
        w0 = drv.clock()
        src = harness.OpenSource(ses.traffic, 3000 + i, w0)
        w1 = w0 + args.seconds
        harness.drive(drv, src, lambda now: now >= w1,
                      lambda k: "window" if k == 0 else "after")
        reqs = [s for s in drv.sent[n0:] if s.phase == "window"]
        half = len(reqs) // 2
        ttft = lambda rs: [s.req.first_token_time - s.due for s in rs  # noqa: E731
                           if s.req.first_token_time is not None]
        early, late = ttft(reqs[:half]), ttft(reqs[half:])
        stages = [st for st in drv.stages if w0 <= st.t1 <= w1]
        prompt, out = harness.window_tokens(drv.stages, drv.sent, w0, w1)
        row = {"rate": rate, "requests": len(reqs),
               "waiting_at_end": sum(1 for s in reqs
                                     if s.req.first_token_time is None),
               "ttft_p90_early_ms": 1e3 * harness.percentile(early, 90)
               if early else None,
               "ttft_p90_late_ms": 1e3 * harness.percentile(late, 90)
               if late else None,
               "stage_ms": 1e3 * args.seconds / max(len(stages), 1),
               "tokens_per_s": (prompt + out) / args.seconds,
               "live": len(drv.live)}
        print(json.dumps(row), flush=True)
    drv.cancel_all()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
