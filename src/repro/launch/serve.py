"""Batched serving driver with MultiVic-style static step schedule.

Serving is where the paper's time-predictability matters most: each
decode step executes the same static program, so the runtime prints the
WCET bound per step (from core.tpu_mapping) next to the measured step
times and reports the observed jitter — the datacenter analogue of the
paper's Fig. 4 variability measurement.

The step program itself comes from a resolved **serving plan**
(tuning.model): prefill chunk sizes, scan-vs-unroll for the decode
layer loop, and the decode weight-pass tile pins.  Resolution follows
the kernel-wrapper precedence — explicit ``--chunk-q``/``--chunk-kv``
flags > the tuned plan cached by ``scripts/tune.py --model`` > shape-
safe defaults — and the WCET bound/deadline are built from the SAME
plan via ``serve_step_schedule``, so the printed bound tracks the plan
actually served.  Prefill and the decode step are AOT-compiled
(``compat.aot_compile``) with a donated KV cache before the timed
region, so every timed step — including the first — runs the compiled
program.

The WCET bound also becomes a *deadline*: every decode step is checked
against ``wcet * --deadline-slack`` (or an explicit ``--deadline-ms``)
and overruns walk the resilience ladder — record, then warn, then shed
(halve) the batch — so overload degrades on a pre-planned path instead
of queueing unboundedly (resilience.DeadlineMonitor; summary printed
next to the jitter stats).

On a TPU the bound is priced against that chip's entry in
``core.tpu_mapping.CHIPS`` (an unknown ``device_kind`` raises); on the
CPU it stays the v5e target.  ``run(parse_args([...]))`` is the same
path for scripts (``chip_smoke.py``): it returns what ``main`` prints.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --batch 4 --prompt-len 64 --gen 32

Set ``REPRO_TRACE=/path/dir`` to write a JAX profiler trace of the
served batch there (``jax.profiler.trace``): the host's phases as
annotations (``prefill``, ``decode_step``, ``sample_sync``), inside
them the step programs' dispatches (``prefill_dispatch``,
``decode_dispatch``), and on the device's clock the operations of
``jit_prefill`` and ``jit_decode_step``, each named by its HLO
instruction (``repro.obs.op_blocks`` maps those to the model's blocks).
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.configs import get_config
from repro.core.tpu_mapping import (V5E, TPUChip, chip_for,
                                    serve_step_schedule, tpu_wcet)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import reduced_config
from repro.models import lm as lm_mod
from repro.models.lm import RunOptions
from repro.resilience.deadline import DeadlineMonitor
from repro.tuning.model import ModelProblem, resolve_model_plan
from repro.tuning.plan import plan_sig


def shed_batch(cfg, cache, tok, n_new: int, cache_len: int,
               windowed: bool = False):
    """Drop the tail of the batch (graceful degradation).

    Spec-driven, not heuristic: ``lm.cache_spec`` names the logical
    axes of every cache leaf, so we slice exactly the axis labelled
    ``batch`` (stacked-layer caches put it at index 1, behind the
    ``stack`` axis) and leave everything else alone."""
    b_old = tok.shape[0]
    assert 0 < n_new < b_old, (n_new, b_old)
    spec = lm_mod.cache_spec(cfg, b_old, cache_len, windowed)

    def shed(par, x):
        if "batch" not in par.axes:
            return x
        ax = par.axes.index("batch")
        return jax.lax.slice_in_dim(x, 0, n_new, axis=ax)

    return jax.tree.map(shed, spec, cache), tok[:n_new]


def plan_wcet_s(cfg, plan: dict, batch: int, n_params: int,
                chip: TPUChip = V5E) -> float:
    """The per-step WCET bound for the decode weight pass under the
    served plan's tile pins — the single source for both the printed
    bound and the derived deadline (tested: changing the plan's pins
    must change this number)."""
    sched = serve_step_schedule(batch, cfg.d_model, n_params, plan=plan,
                                chip=chip)
    return tpu_wcet(sched, chip)


class StepProgram:
    """A compiled step program whose every call the host makes inside
    the profiler annotation ``span``, so a profiler trace shows how long
    the host takes to dispatch it; ``compiled`` is the executable (its
    text maps the trace's operations to blocks: ``repro.obs.op_blocks``)."""

    def __init__(self, compiled, span: str):
        self.compiled, self.span = compiled, span

    def __call__(self, *args):
        with jax.profiler.TraceAnnotation(self.span):
            return self.compiled(*args)


def compile_step_fns(cfg, params, batch, opts: RunOptions,
                     prompt_len: int):
    """AOT-compile prefill and the donated-cache decode step for the
    shapes in ``batch``; returns ``(prefill, step, compile_s)``: the two
    ``StepProgram``s (modules ``jit_prefill`` and ``jit_decode_step``,
    dispatched inside ``prefill_dispatch`` and ``decode_dispatch``)
    ready to call, and their compile seconds.

    ``aot_compile`` populates nothing implicit — the returned compiled
    objects themselves must be called — which is exactly what keeps
    compilation out of the timed region (and off the jitter stats)."""
    def prefill(p, b):
        return lm_mod.prefill(cfg, p, b, opts)

    def decode_step(p, c, t, i):
        return lm_mod.decode_step(cfg, p, c, t, i, opts)

    device = next(iter(jax.tree.leaves(params)[0].sharding.device_set))
    t0 = time.monotonic()
    prefill_c = compat.aot_compile(jax.jit(prefill), params, batch)
    t1 = time.monotonic()
    _, cache = jax.eval_shape(prefill, params, batch)
    tok = jax.ShapeDtypeStruct(batch["tokens"].shape[:1], jnp.int32)
    step_c = compat.aot_compile(
        compat.donated_jit(decode_step, donate_argnums=(1,),
                           platform=device.platform),
        params, cache, tok, jnp.int32(prompt_len))
    t2 = time.monotonic()
    return (StepProgram(prefill_c, "prefill_dispatch"),
            StepProgram(step_c, "decode_dispatch"),
            {"prefill": t1 - t0, "decode": t2 - t1})


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--chunk-q", type=int, default=None,
                    help="explicit prefill q-chunk (overrides the "
                         "tuned serving plan)")
    ap.add_argument("--chunk-kv", type=int, default=None,
                    help="explicit prefill kv-chunk (overrides the "
                         "tuned serving plan)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="explicit per-step deadline; 0 = derive from "
                         "the WCET bound")
    ap.add_argument("--deadline-slack", type=float, default=50.0,
                    help="deadline = WCET bound x slack (the bound "
                         "targets the TPU mapping; on other backends "
                         "the slack absorbs the platform gap)")
    return ap.parse_args(argv)


def run(args: argparse.Namespace) -> dict:
    """Serve one batch: prefill the prompts, then ``args.gen`` decode
    steps under the deadline ladder.

    Returns the model and plan it served (``cfg``, ``params``,
    ``plan``, ``plan_source``), the inputs and outputs (``prompt``
    [B, P]; ``first_token`` [B], the prefill's greedy token;
    ``generated``, one [B'] token array per decode step, B' < B after a
    shed; ``prefill_logits`` and the last step's ``logits``), the
    timings (``compile_s``, ``prefill_s``, ``step_s``), the WCET bound
    ``wcet_s`` with the ``chip`` it was priced on, and the deadline
    monitor's ``deadline`` summary.  With ``REPRO_TRACE`` set, the
    prefill and the decode steps run under the JAX profiler, which
    writes its trace into that directory."""
    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced_config(cfg, args)
    B, P, G = args.batch, args.prompt_len, args.gen
    total = P + G
    chip = chip_for(jax.devices()[0])

    # serving plan: explicit flags > tuned cache entry > defaults
    problem = ModelProblem(
        args.arch, B, P, G,
        layers=0 if args.full else args.layers,
        d_model=args.d_model, vocab=args.vocab)
    resolved = resolve_model_plan(cfg, problem, {
        "chunk_q": args.chunk_q, "chunk_kv": args.chunk_kv})
    plan, plan_source = resolved["plan"], resolved["source"]
    opts = RunOptions(chunk_q=int(plan["chunk_q"]),
                      chunk_kv=int(plan["chunk_kv"]),
                      cache_len=total, remat=False,
                      decode_scan=bool(plan["decode_scan"]))

    key = jax.random.PRNGKey(0)
    params = lm_mod.init_params(cfg, key)
    tokens = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(key, (B, P, cfg.d_model))

    # static-schedule WCET bound for the decode weight pass, built from
    # the SAME plan the steps will execute, computed up front so it can
    # serve as the step deadline
    n_p = lm_mod.param_count(cfg)
    wcet_s = plan_wcet_s(cfg, plan, B, n_p, chip)
    deadline_s = (args.deadline_ms / 1e3 if args.deadline_ms > 0
                  else wcet_s * args.deadline_slack)
    dmon = DeadlineMonitor(deadline_s=deadline_s)

    # all compilation happens here, before anything is timed
    prefill_c, step_c, compile_s = compile_step_fns(cfg, params, batch,
                                                    opts, P)

    trace_dir = os.environ.get("REPRO_TRACE")
    with (jax.profiler.trace(trace_dir) if trace_dir
          else contextlib.nullcontext()):
        r = _serve_batch(cfg, args, params, batch, opts, prefill_c, step_c,
                         dmon)
    return {"cfg": cfg, "params": params, "plan": plan,
            "plan_source": plan_source, "prompt": np.asarray(tokens),
            "compile_s": compile_s, "wcet_s": wcet_s, "chip": chip,
            "deadline": dmon.summary(), **r}


def _serve_batch(cfg, args, params, batch, opts, prefill_c, step_c,
                 dmon) -> dict:
    """The timed part of ``run``: the prefill, then the decode steps
    under the deadline ladder, each phase inside the profiler
    annotation the benchmark's serving loop gives it (``prefill``,
    ``decode_step``, ``sample_sync``)."""
    annotate = jax.profiler.TraceAnnotation
    P, G = args.prompt_len, args.gen
    deadline_s = dmon.deadline_s
    t0 = time.monotonic()
    with annotate("prefill"):
        logits, cache = jax.block_until_ready(prefill_c(params, batch))
    t_prefill = time.monotonic() - t0
    prefill_logits = logits

    out = []
    times = []
    with annotate("sample_sync"):
        tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
        first_token = np.asarray(tok)
    for i in range(G):
        t1 = time.monotonic()
        with annotate("decode_step"):
            logits, cache = step_c(params, cache, tok, jnp.int32(P + i))
            logits = jax.block_until_ready(logits)
        t2 = time.monotonic()
        times.append(t2 - t1)
        with annotate("sample_sync"):
            tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
            out.append(np.asarray(tok))
        action = dmon.observe(i, t2 - t1)
        if action == "warn":
            print(f"deadline overrun at decode step {i}: "
                  f"{(t2 - t1) * 1e3:.2f} ms > "
                  f"{deadline_s * 1e3:.2f} ms")
        elif action == "shed" and tok.shape[0] > 1:
            n_new = tok.shape[0] // 2
            print(f"deadline ladder: shedding batch "
                  f"{tok.shape[0]} -> {n_new} at decode step {i}")
            cache, tok = shed_batch(cfg, cache, tok, n_new, P + G,
                                    opts.windowed_cache)
            # new batch shape = new program: re-AOT-compile outside the
            # per-step timing so the shed path stays compile-free too
            shed_batch_dict = {k: v[:n_new] for k, v in batch.items()}
            _, step_c, _ = compile_step_fns(cfg, params, shed_batch_dict,
                                            opts, P)

    return {"first_token": first_token, "generated": out,
            "prefill_logits": prefill_logits, "logits": logits,
            "prefill_s": t_prefill,
            # AOT warm-up means step 0 is a real step: every sample
            # counts
            "step_s": np.array(times)}


def main():
    args = parse_args()
    enable_compile_cache()
    r = run(args)
    B, P, plan, times, out = (args.batch, args.prompt_len, r["plan"],
                              r["step_s"], r["generated"])
    print(f"serving plan [{r['plan_source']}]: {plan_sig(plan)}")
    print(f"prefill: {r['prefill_s']*1e3:.1f} ms for {B}x{P} tokens")
    print(f"decode:  median {np.median(times)*1e3:.2f} ms/step  "
          f"std {times.std()*1e3:.3f} ms  "
          f"jitter(max-min) {(times.max()-times.min())*1e3:.3f} ms")
    shapes = {o.shape for o in out}
    if len(shapes) == 1:
        print(f"generated shape: {np.stack(out, 1).shape}")
    else:
        print(f"generated: {len(out)} steps, batch shed to "
              f"{out[-1].shape[0]} (started at {B})")

    print(f"TPU-target WCET bound per step (weight pass, "
          f"plan tiles {plan['mm_bm']}x{plan['mm_bn']}): "
          f"{r['wcet_s']*1e3:.3f} ms")
    s = r["deadline"]
    print(f"deadline: {s['deadline_s']*1e3:.3f} ms/step  "
          f"overruns {s['overruns']}/{len(times)}  "
          f"ladder record/warn/shed "
          f"{s['n_record']}/{s['n_warn']}/{s['n_shed']}  "
          f"worst overrun {s['worst_overrun_s']*1e3:.3f} ms")
    if os.environ.get("REPRO_TRACE"):
        print(f"trace: profiler trace -> {os.environ['REPRO_TRACE']}")


if __name__ == "__main__":
    main()
