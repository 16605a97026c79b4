"""Run one cell of the benchmark once, on the TPU chips of this machine.

    python3 chipbench/run.py --workload qwen2-0.5b.decode --seed 7 \
        --seconds 30 --trace 0

from the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration (``chipbench/configs``) and a
traffic mix (``chipbench/traffic``).  The run makes the weights and the
prompts from ``--seed``, compiles or loads from JAX's persistent cache in
``<checkout>/.jax_cache`` (or ``$JAX_COMPILATION_CACHE_DIR``), warms up,
serves closed-loop batches for ``--seconds``, and with ``--trace 1``
serves a further few seconds under the profiler.  It then checks a
sample of the served requests against the plain float32 reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, it
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import catalog
    from chipbench.cell import NoChip, run_cell
    cell = catalog.cell(args.workload, ROOT)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       T_PROC)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
