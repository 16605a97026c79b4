"""End-to-end behaviour tests for the paper's system: the static
schedule pipeline (schedule -> simulate -> WCET) and its TPU mapping,
exercised through the public API."""
import jax
import jax.numpy as jnp

from repro.core import (MatmulProblem, build_matmul_schedule, run_many,
                        schedule_totals, simulate, wcet)
from repro.configs.multivic_paper import OCTA


def test_end_to_end_schedule_pipeline():
    prob = MatmulProblem(256, 256, 256)
    sched = build_matmul_schedule(OCTA, prob)
    totals = schedule_totals(sched)
    assert totals["macs"] == 256 ** 3
    stats = run_many(sched, OCTA, n_runs=5)
    bound = wcet(sched, OCTA)
    assert stats["max"] <= bound
    assert stats["std"] < 1e-3 * stats["median"]   # time-predictable


def test_kernel_agrees_with_simulated_workload():
    """The Pallas kernel computes the same problem the schedule
    describes — numerics via ref, work accounting via schedule."""
    from repro.kernels.spm_matmul.ops import matmul
    from repro.kernels.spm_matmul.ref import matmul_ref
    n = 256
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
    got = matmul(a, b, bm=128, bn=128)
    want = matmul_ref(a, b)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-3
    sched = build_matmul_schedule(OCTA, MatmulProblem(n, n, n))
    assert schedule_totals(sched)["macs"] == n ** 3


def test_serving_is_time_predictable_by_construction():
    """Static decode program: two runs of the same step are identical
    (no data-dependent shapes anywhere)."""
    from conftest import TINY_OPTS, tiny_cfg
    from repro.models import decode_step, init_cache, init_params
    cfg = tiny_cfg("qwen2-0.5b", num_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, 2, 32)
    tok = jnp.array([3, 5], jnp.int32)
    l1, c1 = decode_step(cfg, params, cache, tok, 8, TINY_OPTS)
    l2, c2 = decode_step(cfg, params, cache, tok, 8, TINY_OPTS)
    assert jnp.array_equal(l1, l2)
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        assert jnp.array_equal(a, b)


def _serve_args(*extra):
    from repro.launch import serve
    return serve.parse_args(["--batch", "2", "--prompt-len", "16",
                             "--layers", "2", "--d-model", "64",
                             "--vocab", "256", *extra])


def test_serve_run_returns_full_batch_result():
    """``launch.serve.run`` is the scripted entry point: one batch served
    whole (no deadline pressure) comes back with every field chip_smoke
    checks."""
    import numpy as np

    from repro.core.tpu_mapping import V5E
    from repro.launch import serve
    r = serve.run(_serve_args("--gen", "3", "--deadline-ms", "1e6"))
    assert r["prompt"].shape == (2, 16)
    assert r["first_token"].shape == (2,)
    assert [g.shape for g in r["generated"]] == [(2,)] * 3
    assert r["step_s"].shape == (3,) and r["prefill_s"] > 0
    assert set(r["compile_s"]) == {"prefill", "decode"}
    assert r["plan_source"] == "defaults" and r["chip"] is V5E
    assert r["wcet_s"] > 0 and r["deadline"]["n_shed"] == 0
    v = r["cfg"].vocab_size
    assert np.isfinite(np.asarray(r["logits"][:, :v])).all()
    assert np.isfinite(np.asarray(r["prefill_logits"][:, :v])).all()


def test_serve_run_reports_a_deadline_shed():
    """A shed must be visible in the result (smaller rows after it, and
    the monitor's count), so a smoke run can refuse it."""
    from repro.launch import serve
    r = serve.run(_serve_args("--gen", "5", "--deadline-ms", "1e-6"))
    rows = [g.shape[0] for g in r["generated"]]
    assert rows[0] == 2 and rows[-1] == 1, rows
    assert r["deadline"]["n_shed"] >= 1


def test_step_takes_the_prefills_cache_and_then_its_own():
    """The compiled decode step takes the compiled prefill's cache, and
    then its own, in the layout each returns it in: each attention K/V
    leaf sequence-last (``blocks.kv_cache_spec``) in the default layout;
    its logits and cache are the model's own step's."""
    import numpy as np

    from conftest import tiny_cfg
    from repro.launch import serve
    from repro.models import lm
    from repro.models.lm import RunOptions
    cfg = tiny_cfg("qwen2-0.5b", num_layers=2, dtype="float32")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab_size)}
    opts = RunOptions(chunk_q=8, chunk_kv=8, cache_len=20, remat=False,
                      decode_scan=True)
    prefill, step, _ = serve.compile_step_fns(cfg, params, batch, opts, 16)
    fmt = step.compiled.input_formats[0][1]
    assert prefill.compiled.output_formats[1] == fmt
    assert step.compiled.output_formats[1] == fmt
    logits, cache = prefill(params, batch)
    _, want_cache = lm.prefill(cfg, params, batch, opts)
    assert cache["stage0"]["pos0"]["k"].shape == (2, 2, 2, 32, 20)
    assert cache["stage0"]["pos0"]["k"].shape == \
        want_cache["stage0"]["pos0"]["k"].shape
    for i in range(4):
        tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
        logits, cache = step(params, cache, tok, jnp.int32(16 + i))
        want, want_cache = lm.decode_step(cfg, params, want_cache, tok,
                                          16 + i, opts)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert jax.tree.map(lambda c: c.format, cache) == fmt
    for g, w in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_serve_run_refuses_unknown_tpu_kind(monkeypatch):
    """On a TPU that the peak table lacks, serving raises before it
    prices a bound against the wrong chip."""
    import pytest
    from types import SimpleNamespace

    from repro.launch import serve
    monkeypatch.setattr(serve.jax, "devices", lambda *a: [
        SimpleNamespace(platform="tpu", device_kind="TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        serve.run(_serve_args("--gen", "2"))


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and
    the helper sets nothing."""
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    """Otherwise the cache sits at one fixed, git-ignored path in the
    repository (never a temp name, pid or time)."""
    import pathlib

    from repro.launch.compile_cache import enable_compile_cache
    repo = pathlib.Path(__file__).resolve().parent.parent
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert enable_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert pathlib.Path(first) == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
