"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and record memory / cost / collective
analysis.  This is the proof that the distribution config is coherent
without real hardware (see DESIGN.md and EXPERIMENTS.md §Dry-run).

NOTE: the environment lines below must run before ANY other import —
jax locks the platform and device count on first init, and the dry-run
needs 512 placeholder CPU host devices.  Pinning the CPU keeps this
tool off an attached TPU (one process per chip; a 16x16 mesh cannot be
built from one chip).  Do not set these globally.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k \
      [--multi-pod] [--out experiments/dryrun]
  python -m repro.launch.dryrun --all [--multi-pod both]   # orchestrator
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

from repro.analysis.hlo import summarize_compiled
from repro.compat import cost_analysis
from repro.configs import SHAPES, TrainConfig, get_config, supported_shapes
from repro.configs.all_archs import ALL_ARCH_IDS
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, run_options
from repro.models import lm as lm_mod
from repro.models.lm import RunOptions
from repro.optim.adamw import make_train_step

OUT_DEFAULT = "experiments/dryrun"


def step_fn_for(cfg, shape, opts: RunOptions, variant: str = "baseline"):
    if shape.kind == "train":
        micro = 4 if "micro4" in variant else 0
        tstep = make_train_step(cfg, TrainConfig(microbatch=micro), opts)

        def train_step(params, opt_state, batch):
            return tstep(params, opt_state, batch)
        return train_step, (0, 1)        # donate params+opt

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return lm_mod.prefill(cfg, params, batch, opts)
        return prefill_step, ()

    def serve_step(params, cache, token, pos):
        return lm_mod.decode_step(cfg, params, cache, token, pos, opts)
    return serve_step, (1,)              # donate cache


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: pathlib.Path, variant: str = "baseline") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    opts = run_options(cfg, shape, mesh, variant)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        "kind": shape.kind,
        "variant": variant,
        "status": "unknown",
    }
    t0 = time.time()
    try:
        step, donate = step_fn_for(cfg, shape, opts, variant)
        specs = input_specs(cfg, shape, mesh, variant)
        with mesh:
            lowered = jax.jit(step, donate_argnums=donate).lower(*specs)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
        print(compiled.memory_analysis())
        ca = cost_analysis(compiled)
        print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
        rec.update(summarize_compiled(compiled))
        rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = repr(e)
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    out_dir.mkdir(parents=True, exist_ok=True)
    prefix = "" if variant == "baseline" else f"{variant}__"
    fname = f"{prefix}{arch}__{shape_name}__{rec['mesh']}.json"
    (out_dir / fname).write_text(json.dumps(rec, indent=1))
    print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']}: "
          f"{rec['status']} ({rec['total_s']}s)")
    return rec


def all_cells(which_meshes=("single", "multi")):
    for arch in ALL_ARCH_IDS:
        cfg = get_config(arch)
        for shape_name in supported_shapes(cfg):
            for m in which_meshes:
                yield arch, shape_name, m == "multi"


def orchestrate(args) -> int:
    """Run every cell in a subprocess (isolated jax state; one failure
    doesn't kill the sweep)."""
    out = pathlib.Path(args.out)
    meshes = {"single": ("single",), "multi": ("multi",),
              "both": ("single", "multi")}[args.multi_pod]
    failures = []
    for arch, shape_name, mp in all_cells(meshes):
        tag = f"{arch}__{shape_name}__{'2x16x16' if mp else '16x16'}"
        f = out / f"{tag}.json"
        if f.exists() and not args.force:
            rec = json.loads(f.read_text())
            if rec.get("status") == "ok":
                print(f"[skip] {tag} (cached ok)")
                continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape_name, "--out", args.out]
        if mp:
            cmd.append("--multi-pod")
        r = subprocess.run(cmd, env={**os.environ})
        if r.returncode != 0:
            failures.append(tag)
    if failures:
        print("FAILURES:", failures)
        return 1
    print("dry-run sweep complete")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", nargs="?", const="multi",
                    default="single",
                    choices=["single", "multi", "both"], dest="multi_pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default=OUT_DEFAULT)
    args = ap.parse_args()
    if args.all:
        sys.exit(orchestrate(args))
    assert args.arch and args.shape, "--arch/--shape or --all"
    rec = run_cell(args.arch, args.shape, args.multi_pod == "multi",
                   pathlib.Path(args.out), args.variant)
    sys.exit(0 if rec["status"] == "ok" else 1)


if __name__ == "__main__":
    main()
