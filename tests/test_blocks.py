"""The served step programs name their blocks: the scopes reach the
compiled HLO, each HLO operation maps to its innermost block, the step
programs are compiled as named modules and dispatched inside profiler
annotations, and ``serve.run`` writes a profiler trace whose host spans
carry the serving loop's phase names."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import BLOCKS, op_blocks, op_names
from repro.obs.blocks import block_of

PROGRAMS = {"prefill": "jit_prefill", "decode": "jit_decode_step"}


@pytest.fixture(scope="module")
def steps():
    """qwen2-0.5b's architecture at test size through
    ``serve.compile_step_fns``, with the arguments of one call each."""
    from repro.configs import get_config
    from repro.launch import serve
    from repro.launch.train import reduced_config
    from repro.models import lm
    from repro.models.lm import RunOptions
    args = serve.parse_args(["--batch", "2", "--prompt-len", "16",
                             "--layers", "2", "--d-model", "64",
                             "--vocab", "256"])
    cfg = reduced_config(get_config("qwen2-0.5b"), args)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab_size)}
    opts = RunOptions(chunk_q=8, chunk_kv=8, cache_len=20, remat=False,
                      decode_scan=True)
    prefill, step, _ = serve.compile_step_fns(cfg, params, batch, opts, 16)
    _, cache = prefill.compiled(params, batch)
    tok = jnp.zeros((2,), jnp.int32)
    return {"prefill": (prefill, (params, batch)),
            "decode": (step, (params, cache, tok, jnp.int32(16)))}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_block_scope_reaches_the_compiled_program(steps, program):
    text = steps[program][0].compiled.as_text()
    for block in BLOCKS:
        assert f"/{block}/" in text, block


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_op_map_gives_every_block_operations(steps, program):
    compiled = steps[program][0].compiled
    m = op_blocks(compiled)
    assert set(m.values()) == set(BLOCKS)
    named = op_names(compiled.as_text())
    # every mapped operation is an instruction of the program whose
    # scope path holds its block
    for name, block in m.items():
        assert block in named[name].split("/")


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_step_programs_are_named_modules(steps, program):
    first = steps[program][0].compiled.as_text().splitlines()[0]
    assert first.startswith(f"HloModule {PROGRAMS[program]},"), first


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_dispatch_wrapper_returns_what_the_executable_returns(steps,
                                                              program):
    fn, args = steps[program]
    assert fn.span == {"prefill": "prefill_dispatch",
                       "decode": "decode_dispatch"}[program]

    def fresh():   # the decode step donates its cache
        return jax.tree.map(jnp.copy, args)
    got, want = fn(*fresh()), fn.compiled(*fresh())
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("op_name,block", [
    ("jit(decode_step)/while/body/closed_call/attn_core/exp", "attn_core"),
    ("jit(f)/ffn/attn_proj/dot_general", "attn_proj"),   # innermost
    ("jit(f)/ffn/x/ffn/mul", "ffn"),
    ("jit(prefill)/head/dot_general", "head"),
    ("jit(f)/while/body/dynamic_slice", None),
    ("jit(f)/attn_core_extra/add", None),                # whole names only
])
def test_block_of_takes_the_innermost_block(op_name, block):
    assert block_of(op_name) == block


def test_op_names_reads_each_instruction_with_metadata():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%fused_computation (p: f32[8]) -> f32[8] {",
        '  %exponential.3 = f32[8]{0} exponential(%p), metadata={op_name='
        '"jit(f)/attn_core/exp" source_file="a.py" source_line=3}',
        "}",
        "ENTRY %main (x: f32[8]) -> f32[8] {",
        '  %fusion.12 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_'
        'computation, metadata={op_name="jit(f)/ffn/attn_core/exp"}',
        "  %copy.4 = f32[8]{0} copy(%fusion.12)",
        '  ROOT %add.1 = f32[8]{0} add(%copy.4, %copy.4), metadata={op_'
        'name="jit(f)/add"}',
        "}"])
    assert op_names(text) == {"exponential.3": "jit(f)/attn_core/exp",
                              "fusion.12": "jit(f)/ffn/attn_core/exp",
                              "add.1": "jit(f)/add"}


def test_serve_run_writes_a_profiler_trace(tmp_path, monkeypatch):
    """With ``REPRO_TRACE`` set, ``serve.run`` writes a JAX profiler
    trace there: the serving loop's phases and the step programs'
    dispatches are host spans on the profiler's clock, one per call."""
    from collections import Counter

    from jax.profiler import ProfileData

    from repro.launch import serve
    monkeypatch.setenv("REPRO_TRACE", str(tmp_path))
    gen = 3
    serve.run(serve.parse_args(["--batch", "2", "--prompt-len", "16",
                                "--layers", "2", "--d-model", "64",
                                "--vocab", "256", "--gen", str(gen),
                                "--deadline-ms", "1e6"]))
    paths = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    names = Counter(e.name for p in ProfileData.from_file(paths[0]).planes
                    if "/host:" in p.name for ln in p.lines
                    for e in ln.events)
    assert names["prefill"] == names["prefill_dispatch"] == 1
    assert names["decode_step"] == names["decode_dispatch"] == gen
    assert names["sample_sync"] == gen + 1
