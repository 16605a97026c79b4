"""Roofline runner: lower + compile every cell's cost PIECES on the
single-pod production mesh, compose totals (piece x multiplier), add
the analytic MODEL_FLOPS, and emit the three roofline terms.

The environment lines must precede any other import: jax locks the
platform and device count on first init, and this CPU-only tool must
never take an attached TPU.

Usage:
  python -m repro.launch.roofline_run --arch qwen2-72b --shape train_4k
  python -m repro.launch.roofline_run --all
"""
import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512")))
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import pathlib
import subprocess
import sys
import time
import traceback

import jax

from repro.analysis.flops import active_param_count, model_flops, param_count
from repro.analysis.hlo import summarize_compiled
from repro.analysis.pieces import cost_pieces
from repro.analysis.roofline import compose_pieces, roofline_terms
from repro.configs import SHAPES, get_config, supported_shapes
from repro.configs.all_archs import ALL_ARCH_IDS
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import rules_for, run_options

OUT_DEFAULT = "experiments/roofline"


def run_cell(arch: str, shape_name: str, out_dir: pathlib.Path,
             variant: str = "baseline", opt_overrides=None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=False)
    rules = rules_for(mesh, cfg, shape, variant)
    opts = run_options(cfg, shape, mesh, variant,
                       **(opt_overrides or {}))
    rec = {"arch": arch, "shape": shape_name, "mesh": "16x16",
           "chips": 256, "variant": variant, "status": "unknown",
           "pieces": []}
    t0 = time.time()
    try:
        pieces = cost_pieces(cfg, shape, rules, opts)
        for pc in pieces:
            t1 = time.time()
            with mesh:
                compiled = jax.jit(pc.fn).lower(*pc.specs).compile()
            prec = {"name": pc.name, "multiplier": pc.multiplier,
                    "compile_s": round(time.time() - t1, 2)}
            prec.update(summarize_compiled(compiled))
            rec["pieces"].append(prec)
        comp = compose_pieces(rec["pieces"])
        rec["composed"] = comp
        from repro.analysis.bytes_model import analytic_bytes
        wsh = 16 if "serving_tp" in variant else 0
        ab = analytic_bytes(cfg, shape, weight_shards=wsh)
        rec["analytic_bytes"] = ab
        # analytic (flash-tiled) bytes determine the memory term; the
        # HLO-composed bytes are reported as the unfused upper bound.
        rec["terms"] = roofline_terms(comp["flops"], ab["total"],
                                      comp["collective_bytes"])
        rec["terms_hlo_bytes"] = roofline_terms(
            comp["flops"], comp["bytes_accessed"],
            comp["collective_bytes"])
        mf = model_flops(cfg, shape)
        rec["model_flops_global"] = mf
        rec["model_flops_per_dev"] = mf / 256
        rec["params_total"] = param_count(cfg)
        rec["params_active"] = active_param_count(cfg)
        rec["useful_ratio"] = (mf / 256) / comp["flops"] \
            if comp["flops"] else 0.0
        rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = repr(e)
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    out_dir.mkdir(parents=True, exist_ok=True)
    prefix = "" if variant == "baseline" else f"{variant}__"
    (out_dir / f"{prefix}{arch}__{shape_name}.json").write_text(
        json.dumps(rec, indent=1))
    t = rec.get("terms", {})
    print(f"[roofline] {arch} x {shape_name}: {rec['status']} "
          f"dominant={t.get('dominant')} bound={t.get('bound_s', 0):.4f}s "
          f"({rec['total_s']}s)")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default=OUT_DEFAULT)
    args = ap.parse_args()
    out = pathlib.Path(args.out)
    if args.all:
        failures = []
        for arch in ALL_ARCH_IDS:
            for shape_name in supported_shapes(get_config(arch)):
                f = out / f"{arch}__{shape_name}.json"
                if f.exists() and not args.force:
                    if json.loads(f.read_text()).get("status") == "ok":
                        print(f"[skip] {arch} x {shape_name}")
                        continue
                cmd = [sys.executable, "-m", "repro.launch.roofline_run",
                       "--arch", arch, "--shape", shape_name,
                       "--out", args.out]
                if subprocess.run(cmd, env={**os.environ}).returncode:
                    failures.append((arch, shape_name))
        print("FAILURES:" if failures else "roofline sweep complete",
              failures or "")
        sys.exit(1 if failures else 0)
    rec = run_cell(args.arch, args.shape, out, args.variant)
    sys.exit(0 if rec["status"] == "ok" else 1)


if __name__ == "__main__":
    main()
