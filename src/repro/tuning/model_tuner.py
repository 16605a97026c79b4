"""End-to-end measurement of model serving plans.

Same pipeline as the kernel autotuner one level up: enumerate ->
prune (VMEM + roofline, tuning.model) -> measure -> persist.  The
measured unit is a *full serve pass* — one prefill plus ``gen``
AOT-compiled decode steps with a donated KV cache — timed by the same
GC-quiesced ``measure_callable`` the kernel tuner uses, so a warm
cache still means zero measurement spans on the trace.

Compilation is hoisted out of the timed region entirely: the runner
builds params once, AOT-compiles prefill and the decode step as the
server does (``launch.serve.compile_step_fns``), and the thunk only
executes the compiled programs.  That is what lets p99/CoV of the pass
speak for the plan rather than for compile jitter.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

from repro.obs import JitterStats, TraceRecorder
from repro.tuning.measure import measure_callable, select_plan
from repro.tuning.model import (ModelProblem, default_model_plan,
                                enumerate_model_candidates,
                                model_analytic_cost_s, model_cache_key,
                                model_feasible, problem_config)
from repro.tuning.plan import Plan, plan_sig
from repro.tuning.plan_cache import PlanCache


@dataclass(frozen=True)
class ModelTuneResult:
    problem: ModelProblem
    plan: Plan
    source: str                       # "cache" | "measured"
    key: str
    measured: int                     # timed passes performed (0 = warm)
    candidates: int
    feasible: int
    pruned_to: int
    stats: Optional[JitterStats] = None          # winning plan, full pass
    default_plan: Optional[Plan] = None
    default_stats: Optional[JitterStats] = None  # always measured cold


def us_per_token(stats: JitterStats, problem: ModelProblem) -> float:
    """Median full-pass latency amortized over the generated tokens."""
    return stats.median / max(1, problem.gen)


def make_serve_runner(cfg, problem: ModelProblem,
                      plan: Plan) -> Callable[[], None]:
    """A zero-arg thunk executing one full serve pass (prefill +
    ``gen`` decode steps) under ``plan``, with all compilation done
    before the thunk is returned."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import compile_step_fns
    from repro.models import lm as lm_mod
    from repro.models.lm import RunOptions

    B, P, G = problem.batch, problem.prompt_len, problem.gen
    opts = RunOptions(chunk_q=int(plan["chunk_q"]),
                      chunk_kv=int(plan["chunk_kv"]),
                      cache_len=P + G, remat=False,
                      decode_scan=bool(plan["decode_scan"]))

    key = jax.random.PRNGKey(B + P + G)
    params = lm_mod.init_params(cfg, key)
    tokens = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(key, (B, P, cfg.d_model))

    prefill_c, step_c, _ = compile_step_fns(cfg, params, batch, opts, P)

    def run() -> None:
        logits, cache = prefill_c(params, batch)
        tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
        for i in range(G):
            logits, cache = step_c(params, cache, tok, jnp.int32(P + i))
            tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
        jax.block_until_ready(tok)

    return run


def model_shortlist(cfg, problem: ModelProblem,
                    max_candidates: int = 4) \
        -> Tuple[List[Plan], int, int]:
    """Enumerate, VMEM-filter, rank by the analytic serve-pass bound;
    the default plan is always measured (it is the tuned-vs-default
    baseline, not just a fallback)."""
    cands = enumerate_model_candidates(cfg, problem)
    feas = [c for c in cands if model_feasible(cfg, problem, c)]
    ranked = sorted(feas, key=lambda c: (
        model_analytic_cost_s(cfg, problem, c), plan_sig(c)))
    keep = ranked[:max(1, max_candidates)]
    default = default_model_plan(cfg, problem)
    if default not in keep:
        keep.append(default)
    return keep, len(cands), len(feas)


def tune_model(problem: ModelProblem, *,
               cache: Optional[PlanCache] = None,
               reps: int = 5, warmup: int = 1, max_candidates: int = 4,
               tie_rel: float = 0.05, force: bool = False,
               trace: Optional[TraceRecorder] = None) -> ModelTuneResult:
    """Tune one serving problem end-to-end, consulting/updating the
    shared plan cache under the ``model|`` namespace.

    A warm cache short-circuits before any jax work (``measured == 0``,
    no spans on ``trace``).  On a cold run the result carries both the
    winner's stats and the default plan's, so callers can print the
    tuned-vs-default step comparison without re-measuring.
    """
    if cache is None:
        from repro.tuning.runtime import active_cache
        cache = active_cache()
    key = model_cache_key(problem)
    if not force:
        cached = cache.get(key)
        if cached is not None:
            return ModelTuneResult(problem, cached, "cache", key,
                                   measured=0, candidates=0, feasible=0,
                                   pruned_to=0)

    cfg = problem_config(problem)
    keep, n_cands, n_feas = model_shortlist(cfg, problem, max_candidates)
    default = default_model_plan(cfg, problem)
    results: List[Tuple[Plan, JitterStats]] = []
    for plan in keep:
        fn = make_serve_runner(cfg, problem, plan)
        stats = measure_callable(
            fn, reps=reps, warmup=warmup, trace=trace,
            label=f"model/{problem.sig}/{plan_sig(plan)}")
        results.append((plan, stats))
    best_plan, best_stats = select_plan(results, tie_rel=tie_rel)
    default_stats = next(s for p, s in results if p == default)

    cache.put(key, best_plan,
              kernel="model", shape=problem.sig, dtype=problem.dtype,
              objective=best_stats.as_dict(),
              default_objective=default_stats.as_dict(),
              candidates=n_cands, feasible=n_feas,
              measured_plans=len(results), reps=reps)
    cache.save()
    return ModelTuneResult(problem, dict(best_plan), "measured", key,
                           measured=len(results) * max(1, reps),
                           candidates=n_cands, feasible=n_feas,
                           pruned_to=len(results), stats=best_stats,
                           default_plan=dict(default),
                           default_stats=default_stats)
