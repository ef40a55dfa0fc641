import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_FLAGS") or
                           "--xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, fits, and produce its roofline terms.

The FIRST TWO LINES above must run before any jax import (jax locks the
device count on first init). Smoke tests / benches import other modules
and see 1 device; this module is the only place the 512-device world
exists (override with REPRO_XLA_FLAGS for the 8-device test mesh).

Per cell this script:
  1. lowers + compiles the FULL step (train_step / prefill_step /
     decode_step) under production shardings -> compile proof,
     memory_analysis (fits-on-chip check), full-HLO collective schedule;
  2. costs each program segment separately and scales by repeat count
     (compositional roofline; see segment_cost.py for why);
  3. writes artifacts/dryrun/<arch>__<shape>__<mesh>.json (resumable).

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
  python -m repro.launch.dryrun --all --skip-full   # segments only
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch import hlo_stats, segment_cost, steps
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.optim.optimizer import AdamWConfig
from repro.parallel import env

OPT = AdamWConfig(factored=False)
V5E_HBM = 16 * 1024 ** 3


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             skip_full=False, skip_segments=False, head_mode="reduced",
             opt_cfg=OPT, cfg_override=None, tag="",
             serve_weights="train", perf=False):
    cfg = cfg_override or get_config(arch, perf=perf)
    # Replicated serve weights trade per-layer FSDP gathers for local
    # reads: a win when the batch fills the data axis (measured 4.7-42x on
    # decode_32k), a LOSS at B=1 long-context (hillclimb lesson: rwkv6
    # long_500k regressed 25x before this guard).
    if perf and SHAPES[shape_name].kind == "decode"             and SHAPES[shape_name].global_batch >= 16:
        serve_weights = "replicated"
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "skipped": why}
    if mesh_name == "multi":
        mesh = make_production_mesh(multi_pod=True)
    elif mesh_name == "single":
        mesh = make_production_mesh()
    else:  # test meshes like '4x2'
        dims = tuple(int(x) for x in mesh_name.split("x"))
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data",
                                                         "model")
        mesh = make_mesh(dims, axes)
    n_chips = mesh.devices.size

    out = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "n_chips": n_chips, "tag": tag}

    if not skip_full:
        t0 = time.time()
        with env.use_mesh(mesh):
            if shape.kind == "train":
                lowered = steps.lower_train(cfg, opt_cfg, mesh, shape)
            elif shape.kind == "prefill":
                lowered = steps.lower_prefill(cfg, mesh, shape, head_mode,
                                              serve_weights=serve_weights)
            else:
                lowered = steps.lower_decode(cfg, mesh, shape, head_mode,
                                             serve_weights=serve_weights)
            compiled = lowered.compile()
        mem = hlo_stats.memory_report(compiled)
        coll = hlo_stats.collective_bytes(compiled.as_text())
        ca = compiled.cost_analysis()
        # args/out/alias are PER-DEVICE; temp is PROGRAM-WIDE on the
        # host-simulated backend (all partitions share one arena) -> /chips.
        hbm = None
        if mem:
            hbm = (mem.get("argument_size_in_bytes", 0)
                   + mem.get("output_size_in_bytes", 0)
                   - mem.get("alias_size_in_bytes", 0)
                   + mem.get("temp_size_in_bytes", 0) / n_chips)
        out["full"] = {
            "compile_s": round(time.time() - t0, 1),
            "memory": mem,
            "hbm_bytes_per_dev": hbm,
            "fits_v5e_16g": (hbm is not None and hbm < V5E_HBM),
            "collective_schedule": coll,
            "flops_per_dev_scan_body": float(ca.get("flops", 0.0)),
        }

    if not skip_segments:
        t0 = time.time()
        if shape.kind == "train":
            cell = segment_cost.train_cell(cfg, opt_cfg, mesh, shape)
        else:
            cell = segment_cost.serve_cell(cfg, mesh, shape, shape.kind,
                                           serve_weights=serve_weights)
        cell["segment_cost_s"] = round(time.time() - t0, 1)
        out.update(cell)
        mf = segment_cost.model_flops(cfg, shape)
        hlo_flops_global = cell["totals"]["flops_per_dev"] * n_chips
        out["model_flops"] = mf
        out["useful_flops_ratio"] = (mf / hlo_flops_global
                                     if hlo_flops_global else None)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    help="single | multi | both | AxB test mesh")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-full", action="store_true")
    ap.add_argument("--skip-segments", action="store_true")
    ap.add_argument("--head-mode", default="reduced")
    ap.add_argument("--perf", action="store_true",
                    help="apply PERF_PROFILES + decode weight regime")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])

    for arch, shape in cells:
        for mesh_name in meshes:
            name = f"{arch}__{shape}__{mesh_name}".replace("/", "_")
            path = outdir / f"{name}.json"
            if path.exists() and not args.force:
                print(f"[skip] {name} (exists)")
                continue
            t0 = time.time()
            try:
                res = run_cell(arch, shape, mesh_name,
                               skip_full=args.skip_full,
                               skip_segments=args.skip_segments,
                               head_mode=args.head_mode, perf=args.perf,
                               tag="perf" if args.perf else "")
            except Exception as e:  # record failures as artifacts too
                res = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "error": repr(e),
                       "traceback": traceback.format_exc()[-4000:]}
            res["wall_s"] = round(time.time() - t0, 1)
            path.write_text(json.dumps(res, indent=1))
            status = ("SKIP " + res["skipped"][:40] if "skipped" in res
                      else "ERROR " + res.get("error", "")[:80]
                      if "error" in res else
                      f"ok t={res['wall_s']}s "
                      f"bottleneck={res.get('totals', {}).get('bottleneck')}")
            print(f"[{arch} x {shape} x {mesh_name}] {status}", flush=True)


if __name__ == "__main__":
    main()
