"""Run one cell several times, each run its own process, and report the
spread of every metric.

    python3 chipbench/tools/sets.py --workload qwen2-0.5b.decode \
        --seeds 11,12,13,14,15,16 --seconds 30 [--trace 0] [--repeat 2] \
        [--out chiprun_out/sets.jsonl]

``--repeat 2`` runs the seed list twice (two sets with the same seeds).
Every run's result line and the tail of its standard error go to
``--out``, one JSON object per line; a summary of each set follows on
standard output: per metric the values, the median and the spread, the
distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def spread(values) -> float | None:
    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def run_once(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, str(ROOT / "chipbench" / "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.monotonic()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=1500)
    lines = p.stdout.strip().splitlines()
    result = None
    if p.returncode == 0 and lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return {"workload": workload, "seed": seed, "trace": trace,
            "rc": p.returncode, "wall_s": time.monotonic() - t0,
            "result": result, "stdout_head": lines[:-1][-12:],
            "stderr_tail": p.stderr[-3000:]}


def summarize(runs: list) -> dict:
    vals: dict = {}
    for r in runs:
        if r["result"]:
            for k, v in r["result"]["metrics"].items():
                vals.setdefault(k, []).append(v["value"])
    return {k: {"values": v, "median": statistics.median(v),
                "spread": spread(v)} for k, v in vals.items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--out", default="chiprun_out/sets.jsonl")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    out = ROOT / args.out
    os.makedirs(out.parent, exist_ok=True)
    for rep in range(args.repeat):
        runs = []
        for s in seeds:
            r = run_once(args.workload, s, args.seconds, args.trace)
            r["set"] = rep
            runs.append(r)
            with open(out, "a", encoding="utf-8") as f:
                f.write(json.dumps(r) + "\n")
            res = r["result"] or {}
            print(json.dumps({"set": rep, "seed": s, "rc": r["rc"],
                              "wall_s": r["wall_s"],
                              "correct": res.get("correct"),
                              "metrics": {k: v["value"] for k, v in
                                          res.get("metrics", {}).items()},
                              "checks": res.get("checks"),
                              "peak": res.get("device", {}).get(
                                  "memory_peak_bytes")}), flush=True)
            if r["rc"] != 0:
                print(r["stderr_tail"][-1500:], flush=True)
        print(json.dumps({"set": rep, "summary": summarize(runs)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
