"""Smoke run of the serving path on one TPU chip.

Serves qwen2-0.5b at its published widths (24 layers, d_model 896,
vocab 151936; random bf16 weights from seed 0) through the normal
serving entry point, ``repro.launch.serve.run``: a batch of 8
requests, 128-token prompts, 32 generated tokens, prefill chunks pinned
by explicit flags.  It then checks, raising on the first failure:

  * the device is a TPU (there is no CPU fallback);
  * every decode step kept the full batch of 8 (a deadline shed changes
    the program served, so it fails the smoke);
  * the prefill and last decode logits are finite;
  * decode vs the full forward on the chip: with the served tokens
    teacher-forced, the last decode logits match ``forward_hidden`` +
    ``compute_logits`` over the same 160 tokens (the check of
    tests/test_decode_equivalence.py, at full width);
  * prefill vs float32: request 0's prefill logits match the same
    parameters run in float32 on the host's CPU backend.

Earlier lines print the device, compile and step times, the WCET bound
and deadline summary, peak device memory and both reference errors.
The last line is ``{"ok": true, "device": {...}}``, printed only when
every check passed.

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process).
It needs nothing outside the checkout: weights and prompts come from a
seed, and the plan from explicit chunk sizes with the tuned-plan cache
off.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

BATCH, PROMPT, GEN = 8, 128, 32
SERVE_ARGV = ["--arch", "qwen2-0.5b", "--full",
              "--batch", str(BATCH), "--prompt-len", str(PROMPT),
              "--gen", str(GEN), "--chunk-q", "64", "--chunk-kv", "64"]
DECODE_TOL = 2e-2    # tests/test_decode_equivalence.py
PREFILL_TOL = 3e-2   # bf16 policy, tests/conftest.py KERNEL_TOLERANCES


def rel_err(got, want) -> float:
    """Relative max-abs error, scaled by the reference's magnitude."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def decode_vs_forward(r) -> float:
    """Last decode logits vs the full forward over the same tokens, both
    on the default device."""
    import jax
    import numpy as np

    from repro.models import lm
    from repro.models.lm import RunOptions
    cfg, params = r["cfg"], r["params"]
    fed = [r["first_token"]] + r["generated"][:-1]
    tokens = np.concatenate([r["prompt"], np.stack(fed, 1)], axis=1)
    opts = RunOptions(chunk_q=0, chunk_kv=0, remat=False)

    @jax.jit
    def last_logits(p, t):
        x, _, _ = lm.forward_hidden(cfg, p, {"tokens": t}, opts)
        return lm.compute_logits(cfg, p, x[:, -1])

    want = last_logits(params, tokens)
    v = cfg.vocab_size
    return rel_err(r["logits"][:, :v], want[:, :v])


def prefill_vs_f32_cpu(r) -> float:
    """Request 0's prefill logits vs the same parameters in float32 on
    the host's CPU backend."""
    import jax
    import numpy as np

    from repro.models import lm
    from repro.models.lm import RunOptions
    cpu = jax.devices("cpu")[0]
    cfg = dataclasses.replace(r["cfg"], dtype="float32")
    params = jax.device_put(
        jax.tree.map(lambda a: np.asarray(a).astype(np.float32),
                     r["params"]), cpu)
    batch = jax.device_put({"tokens": r["prompt"][:1]}, cpu)
    opts = RunOptions(chunk_q=0, chunk_kv=0, remat=False)
    want, _ = jax.jit(lambda p, b: lm.prefill(cfg, p, b, opts))(params,
                                                                 batch)
    v = cfg.vocab_size
    return rel_err(r["prefill_logits"][:1, :v], want[:, :v])


def smoke(argv=SERVE_ARGV) -> None:
    """Serve once and run every check; prints the measurements."""
    import jax
    import numpy as np

    from repro.launch import serve
    # explicit chunk sizes + built-in defaults; never a tuned-plan cache
    os.environ["REPRO_AUTOTUNE"] = "0"
    r = serve.run(serve.parse_args(argv))
    cfg, steps = r["cfg"], r["step_s"]
    print(f"model: {cfg.name} layers {cfg.num_layers} d_model "
          f"{cfg.d_model} vocab {cfg.vocab_size}; batch {BATCH} x "
          f"({PROMPT} prompt + {GEN} generated)")
    print(f"serving plan [{r['plan_source']}]: {r['plan']}")
    print(f"compile s: prefill {r['compile_s']['prefill']} decode "
          f"{r['compile_s']['decode']}")
    print(f"prefill ms: {r['prefill_s'] * 1e3}")
    print(f"decode ms/step: median {np.median(steps) * 1e3} p99 "
          f"{np.percentile(steps, 99) * 1e3} max {steps.max() * 1e3} "
          f"(n={steps.size})")
    d = r["deadline"]
    print(f"WCET bound ms/step: {r['wcet_s'] * 1e3}; deadline ms/step "
          f"{d['deadline_s'] * 1e3}: overruns {d['overruns']} ladder "
          f"record/warn/shed {d['n_record']}/{d['n_warn']}/{d['n_shed']}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")

    rows = [g.shape[0] for g in r["generated"]]
    if len(rows) != GEN or any(n != BATCH for n in rows) or d["n_shed"]:
        raise AssertionError(f"batch not served whole: rows per step "
                             f"{rows}, sheds {d['n_shed']}")
    v = cfg.vocab_size
    for name in ("prefill_logits", "logits"):
        if not np.isfinite(np.asarray(r[name][:, :v])).all():
            raise AssertionError(f"{name} not finite")

    err_decode = decode_vs_forward(r)
    print(f"decode vs full forward (chip): rel max-abs {err_decode} "
          f"(tol {DECODE_TOL})")
    err_prefill = prefill_vs_f32_cpu(r)
    print(f"prefill vs float32 CPU reference: rel max-abs {err_prefill} "
          f"(tol {PREFILL_TOL})")
    if not err_decode < DECODE_TOL:
        raise AssertionError(f"decode vs forward {err_decode}")
    if not err_prefill < PREFILL_TOL:
        raise AssertionError(f"prefill vs f32 reference {err_prefill}")


def main() -> None:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{dev.platform!r}); refusing to fall back")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(f"device: {dev.device_kind} x{len(jax.devices())}")
    smoke()
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
