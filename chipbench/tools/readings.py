"""The readings a cell's ``token_gap`` limit is set from, several seeds
in one process: each seed is one whole run of the cell
(``cell.run_cell``, the window at ``--seconds``) with the reference
computed at the lower precision ``--control`` put in the program's place
for the comparison.  Each run's ``correct`` is then the control's, and
its ``readings`` hold both widest gaps: the program's and the control's.

    python3 chipbench/tools/readings.py --workload qwen2-0.5b.decode \
        --seeds 101,102,103 [--control fp8] [--seconds 2] \
        [--out chiprun_out/readings.jsonl]

Needs the cell's TPU chips, like a run.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="fp8", choices=("fp8", "int8"))
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default="chiprun_out/readings.jsonl")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    from chipbench import catalog
    from chipbench.cell import run_cell

    cell = catalog.cell(args.workload, ROOT)
    out = ROOT / args.out
    os.makedirs(out.parent, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        res = run_cell(cell, seed, args.seconds, False, t0,
                       control=args.control)
        row = {"workload": args.workload, "seed": seed,
               "control": args.control, "correct": res["correct"],
               "readings": res["readings"], "checks": res["checks"],
               "peak": res["device"]["memory_peak_bytes"],
               "wall_s": time.monotonic() - t0}
        print(json.dumps(row), flush=True)
        with open(out, "a", encoding="utf-8") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
