"""One cell's step programs' device time by block, and the decode step's
launch, from a traced segment like the one ``run.py --trace 1`` takes.

    python3 chipbench/tools/breakdown.py --workload qwen2-0.5b.decode \
        --seeds 7,8 [--seconds 10] [--out chiprun_out/breakdown.jsonl]

Per seed: sets the cell up as a run does (``cell.set_up``), serves
``--seconds`` untraced, then the run's traced segment
(``cell.traced_segment``: ``cell.TRACE_S`` under the profiler, reduced
by ``tracing.reduce`` and ``blocks.reduce`` into the record the
per-layer metrics read).  One JSON line per seed: per step program
the ms per step of each block and ``other``, their sum against the
program's device time, the share of that time in operations with no
``op_name`` at all, and the ``other`` operations that took most; the
median and largest decode launch; and the median and slowest step times
in the window and in the traced segment (what tracing costs).  There is
no reference check: ``correct`` is ``run.py``'s.  Needs the cell's TPU
chips, like a run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def step_gaps(batches, lo: float, hi: float) -> list:
    """Seconds between two tokens of one batch, both inside [lo, hi]."""
    return [t1 - t0 for b in batches for t0, t1 in zip(b.times, b.times[1:])
            if lo <= t0 and t1 <= hi]


def one_seed(cell, seed: int, seconds: float) -> dict:
    from chipbench import blocks, loop
    from chipbench import cell as C
    from chipbench.peaks import peaks_for
    from chipbench.record import Record
    from repro.obs import op_names

    dev = C.devices(cell.chips)[0]
    server, m, prompts = C.set_up(cell, seed)
    gc.collect()
    gc.freeze()
    t0 = time.monotonic()
    batches: list = []
    inflight = loop.serve(server, prompts, t0 + seconds, log=batches)
    rec = Record(arch=cell.arch, dims=m, peaks=peaks_for(dev.device_kind),
                 batch=cell.traffic["batch"],
                 prompt_len=cell.traffic["prompt_len"], setup_s=0.0)
    t1 = time.monotonic()
    C.traced_segment(server, prompts, inflight, batches, rec)
    t2 = time.monotonic()
    gc.unfreeze()
    trace, red = rec.trace, rec.blocks
    served = {"prefill": rec.traced_prefills,
              "decode_step": len(rec.traced_positions)}
    out = {"workload": cell.name, "seed": seed, "served": served}
    for k, attr in blocks.PROGRAMS.items():
        ms = blocks.per_step_ms(red, k, served[k])
        if ms is None or not trace:
            continue
        s = red["steps"][k]
        busy_ms = 1e3 * trace["steps"][k]["busy_s"] / served[k]
        named = op_names(getattr(server, attr).compiled.as_text())
        unscoped = sum(v for name, v in s["other_ops"].items()
                       if name not in named)
        out[k] = {
            "ms_per_step": ms,
            "attn_core": ms.get("attn_core"),
            "matmul": sum(ms.get(b, 0.0) for b in blocks.MATMUL),
            "other": ms[blocks.OTHER],
            "blocks_sum_over_busy": sum(ms.values()) / busy_ms,
            "busy_ms_per_step": busy_ms,
            "no_op_name_share": unscoped / s["busy_s"],
            "other_ops_ms_per_step": [
                [name, 1e3 * v / served[k], named.get(name)]
                for name, v in sorted(s["other_ops"].items(),
                                      key=lambda kv: -kv[1])[:12]]}
    out["decode_launch_ms"] = blocks.launch_ms(red, served["decode_step"])
    if red and red["dispatch_s"]:
        out["decode_launch_max_ms"] = 1e3 * max(red["dispatch_s"])
    for name, (lo, hi) in (("window", (t0, t1)), ("traced", (t1, t2))):
        g = step_gaps(batches, lo, hi)
        if g:
            out[f"{name}_step_ms"] = {
                "n": len(g), "median": 1e3 * statistics.median(g),
                "slowest": [1e3 * x for x in sorted(g)[-3:]]}
    if trace:
        out["device_idle_share"] = 100 * (1 - trace["busy_s"]
                                          / trace["window_s"])
        out["idle_gaps"] = trace["idle_gaps"]
    server.params = server.prefill = server.step = None
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="chiprun_out/breakdown.jsonl")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    from chipbench import catalog

    cell = catalog.cell(args.workload, ROOT)
    out = ROOT / args.out
    os.makedirs(out.parent, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        row = one_seed(cell, seed, args.seconds)
        print(json.dumps(row), flush=True)
        with open(out, "a", encoding="utf-8") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
