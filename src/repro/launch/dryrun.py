"""Multi-pod dry-run (assignment §e): lower + compile every
(architecture × input-shape × mesh) cell against ShapeDtypeStruct stand-ins,
prove the sharding config is coherent, record memory/cost/collective
analysis for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
"""
import os
# must precede every jax-importing import (jax locks the device count on
# init): 512 virtual CPU devices, added to any flags the caller set, and the
# CPU platform pinned so a machine with an accelerator never starts it here
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs.base import RunConfig, shape_applicable, SHAPES
from repro.configs.registry import all_archs, get_config, get_shape
from repro.launch.mesh import make_production_mesh, mesh_info
from repro.launch.roofline import (model_bytes, model_flops,
                                   terms_from_compiled)
from repro.launch.steps import make_cell_step
from repro.training.optimizer import OptConfig


def _mem_analysis_dict(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
    except Exception as e:  # backend-dependent availability
        return {"error": repr(e)}
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "host_generated_code_size_in_bytes",
              "host_argument_size_in_bytes", "host_output_size_in_bytes",
              "host_temp_size_in_bytes"):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = repr(m)
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             run: RunConfig, moe_impl: str = "duplex",
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    rec: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi_pod" if multi_pod else "single_pod",
        "mesh_info": mesh_info(mesh), "moe_impl": moe_impl,
        "run_config": {"remat": run.remat_policy,
                       "seq_shard": run.seq_shard_activations,
                       "microbatch": run.microbatch_size,
                       "compression": run.grad_compression,
                       "moe_sharding": run.moe_sharding,
                       "kv_quant": run.kv_quant},
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    t0 = time.monotonic()
    try:
        fn, in_specs, in_sh, out_sh, meta = make_cell_step(
            cfg, shape, mesh, run, OptConfig(), moe_impl=moe_impl)
        rec["meta"] = meta
        with mesh:
            # serve steps donate the KV cache (in-place append, standard
            # serving practice); train steps donate the optimizer state.
            donate = ()
            if meta.get("kind") == "decode":
                donate = (2,)
            elif meta.get("kind") == "train":
                donate = (0,)
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*in_specs)
            rec["lower_s"] = time.monotonic() - t0
            t1 = time.monotonic()
            compiled = lowered.compile()
            rec["compile_s"] = time.monotonic() - t1
            rec["memory_analysis"] = _mem_analysis_dict(compiled)
            mf = model_flops(cfg, shape)
            mb = model_bytes(cfg, shape)
            terms, sites = terms_from_compiled(compiled, chips, model_fl=mf,
                                               model_by=mb)
            rec["roofline"] = terms.to_dict()
            rec["profile_top"] = [
                {"op": s.op, "flops": s.flops, "bytes": s.bytes,
                 "mult": s.mult, "metadata": s.metadata[:160]}
                for s in sites[:12]]
            # XLA's own cost analysis (undercounts scans) kept as cross-check
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            rec["xla_cost_analysis"] = {
                k: float(ca[k]) for k in ("flops", "bytes accessed")
                if k in ca}
            rec["status"] = "ok"
            if verbose:
                print(compiled.memory_analysis())
                print(rec["xla_cost_analysis"])
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = repr(e)
        rec["traceback"] = traceback.format_exc()
    rec["total_s"] = time.monotonic() - t0
    return rec


def cell_list(archs, shapes, meshes):
    cells = []
    for a in archs:
        for s in shapes:
            if not shape_applicable(a, s):
                cells.append((a, s, None, "skipped"))
                continue
            for m in meshes:
                cells.append((a, s, m, "run"))
    return cells


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default=None, help="arch id (default: all)")
    p.add_argument("--shape", default=None, help="shape id (default: all)")
    p.add_argument("--mesh", default="both",
                   choices=["single", "multi", "both"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default="artifacts/dryrun")
    p.add_argument("--moe-impl", default="duplex",
                   choices=["duplex", "grouped"])
    p.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    p.add_argument("--no-seq-shard", action="store_true")
    p.add_argument("--microbatch", type=int, default=0)
    p.add_argument("--compression", default="none",
                   choices=["none", "int8_ef"])
    p.add_argument("--moe-sharding", default="auto",
                   choices=["auto", "ep", "tp"])
    p.add_argument("--kv-quant", action="store_true",
                   help="int8 KV cache for decode cells (beyond-paper)")
    p.add_argument("--attn-q-block", type=int, default=512)
    p.add_argument("--attn-kv-block", type=int, default=512)
    p.add_argument("--attn-score-bf16", action="store_true")
    p.add_argument("--force", action="store_true")
    p.add_argument("--list", action="store_true")
    p.add_argument("--slice", default=None,
                   help="i:j slice of the cell list (parallel workers)")
    p.add_argument("--tag", default="", help="suffix for output filenames")
    args = p.parse_args(argv)

    archs = [args.arch] if args.arch else list(all_archs())
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    run = RunConfig(microbatch_size=args.microbatch,
                    remat_policy=args.remat,
                    moe_sharding=args.moe_sharding,
                    grad_compression=args.compression,
                    seq_shard_activations=not args.no_seq_shard,
                    kv_quant=args.kv_quant,
                    attn_q_block=args.attn_q_block,
                    attn_kv_block=args.attn_kv_block,
                    attn_score_bf16=args.attn_score_bf16)

    cells = cell_list(archs, shapes, meshes)
    if args.slice:
        i, j = (int(x) if x else None for x in args.slice.split(":"))
        cells = cells[i:j]
    if args.list:
        for c in cells:
            print(c)
        return 0

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape, multi_pod, kind in cells:
        if kind == "skipped":
            name = f"{arch}__{shape}__skipped"
            path = os.path.join(args.out, name + ".json")
            with open(path, "w") as f:
                json.dump({"arch": arch, "shape": shape,
                           "status": "skipped",
                           "reason": "full-attention arch; long_500k requires "
                                     "sub-quadratic attention (DESIGN.md §4)"},
                          f, indent=2)
            print(f"[skip] {arch} × {shape} (full-attention)")
            continue
        mesh_tag = "multi" if multi_pod else "single"
        name = f"{arch}__{shape}__{mesh_tag}"
        if args.tag:
            name += f"__{args.tag}"
        path = os.path.join(args.out, name + ".json")
        if os.path.exists(path) and not args.force:
            with open(path) as f:
                old = json.load(f)
            if old.get("status") == "ok":
                print(f"[cached] {name}")
                continue
        print(f"[run] {name} ...", flush=True)
        rec = run_cell(arch, shape, multi_pod=multi_pod, run=run,
                       moe_impl=args.moe_impl)
        with open(path, "w") as f:
            json.dump(rec, f, indent=2, default=str)
        if rec["status"] == "ok":
            r = rec["roofline"]
            print(f"  ok lower={rec['lower_s']:.1f}s "
                  f"compile={rec['compile_s']:.1f}s "
                  f"dominant={r['dominant']} t_bound={r['t_bound']:.4f}s "
                  f"mfu_frac={r['roofline_fraction']:.3f}", flush=True)
        else:
            failures += 1
            print(f"  ERROR: {rec['error']}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
