"""The TPU compiler's verdict on the served path, without a chip.

Compiles qwen2-0.5b's prefill and donated decode step at published
widths (the shapes ``chip_smoke.py`` serves) and the Pallas kernels at
qwen2-0.5b's projection and attention shapes, for one chip of a
*described* v5e:2x2 topology.  Nothing runs, so this says nothing about
results or times; it catches what interpret mode cannot: VMEM
overflows, unaligned tiles, programs that do not fit in HBM.  It also
reads the structure of the served decode step at the benchmark's decode
shapes: the stacked KV cache is updated in place, with no copy or slice
of a layer's cache or of the whole stack.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports every test module.  Keep these compiles in this one file.
``wkv6`` is absent on purpose: its kernel does not lower for the chip
(see ROADMAP design debts).
"""
import dataclasses
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.configs import get_config
from repro.launch import serve
from repro.models import lm
from repro.models.lm import RunOptions

HBM_BYTES = 16e9                 # one v5e chip
B, P, G = 8, 128, 32             # chip_smoke.py's serve shape


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs in /tmp
    try:
        topo = compat.tpu_topology("v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to verify
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables can be written to the persistent
    # cache but never read back; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.fixture(scope="module")
def qwen2_shapes(one_chip):
    cfg = get_config("qwen2-0.5b")
    opts = RunOptions(chunk_q=64, chunk_kv=64, cache_len=P + G,
                      remat=False, decode_scan=True)
    params = _on(one_chip, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=one_chip)
    batch = {"tokens": tokens, "targets": tokens}
    return cfg, opts, params, batch


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_qwen2_serve_step_compiles_for_v5e(one_chip, qwen2_shapes,
                                           program):
    cfg, opts, params, batch = qwen2_shapes
    prefill = jax.jit(lambda p, b: lm.prefill(cfg, p, b, opts))
    if program == "prefill":
        compiled = prefill.lower(params, batch).compile()
    else:
        _, cache = jax.eval_shape(prefill, params, batch)
        step = jax.jit(
            lambda p, c, t, i: lm.decode_step(cfg, p, c, t, i, opts),
            donate_argnums=(1,))
        tok = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
        pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled = step.lower(params, _on(one_chip, cache), tok,
                              pos).compile()
    used = _device_bytes(compiled)
    assert 0 < used < HBM_BYTES, used


def _scheduled_ops(text: str):
    """``(name, opcode, result dims)`` of every instruction outside the
    fused computations of an optimized HLO module's text."""
    fused = set(re.findall(r"\bfusion\(.*?calls=%?([\w.\-]+)", text))
    comp, out = None, []
    for line in text.splitlines():
        if line and not line[0].isspace() and line.endswith("{"):
            comp = line.replace("ENTRY ", "").lstrip("%").split()[0]
            continue
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*)", line)
        if comp in fused or not m:
            continue
        rest = m.group(2)
        if rest.startswith("("):     # a tuple: its type ends at its ")"
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            ty, rest = rest[:i + 1], rest[i + 1:]
        else:
            ty, _, rest = rest.partition(" ")
        op = re.match(r"\s*([\w\-]+)\(", rest)
        for dims in re.findall(r"\w+\[([\d,]*)\]", ty):
            shape = [int(d) for d in dims.split(",") if d]
            while shape and shape[0] == 1:
                shape = shape[1:]
            out.append((m.group(1), op.group(1) if op else "", shape))
    return out


def _moves(ops, shapes):
    """The copies and slices among ``ops`` whose result is one of
    ``shapes``; fusions are named after the operations fused in them."""
    return [name for name, op, shape in ops if shape in shapes and (
        op in ("copy", "copy-start", "copy-done", "dynamic-slice")
        or op == "fusion" and ("copy" in name or "dynamic-slice" in name))]


DECODE_CELLS = {   # the benchmark's decode cells: batch, prompt, gen
    "qwen2-0.5b": (get_config("qwen2-0.5b"), 128, 256, 1792),
    "deepseek-67b-l4": (dataclasses.replace(get_config("deepseek-67b"),
                                            num_layers=4), 32, 512, 512),
}


@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
def test_decode_step_updates_the_stacked_cache_in_place(one_chip, cell):
    """The served decode step (``serve.compile_step_fns``) writes one
    row into the stacked K/V caches and reads each layer inside
    attention: no copy or slice of a layer's cache or of the whole
    stack, and no cache-sized temporary.  Its temporaries stay below
    one layer's K cache plus one layer's largest weight (the layer
    loop's slice of the stacked weights).  The prefill hands the step
    its cache in the layout the step takes, and the step returns it so:
    the device's default layout, which a program loaded from JAX's
    persistent compilation cache keeps."""
    cfg, b, p, g = DECODE_CELLS[cell]
    opts = RunOptions(chunk_q=512, chunk_kv=512, cache_len=p + g,
                      remat=False, decode_scan=True)
    params = _on(one_chip, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    batch = {"tokens": jax.ShapeDtypeStruct((b, p), jnp.int32,
                                            sharding=one_chip)}
    prefill, step, _ = serve.compile_step_fns(cfg, params, batch, opts, p)
    fmt = step.compiled.input_formats[0][1]
    assert prefill.compiled.output_formats[1] == fmt
    assert step.compiled.output_formats[1] == fmt
    for f in jax.tree.leaves(fmt):
        order = f.layout.major_to_minor
        assert order == tuple(sorted(order)), order

    a = cfg.attention
    layer = [b, a.num_kv_heads, a.head_dim, p + g]
    moves = _moves(_scheduled_ops(step.compiled.as_text()),
                   [layer, [cfg.num_layers] + layer])
    assert moves == [], moves
    bf16 = 2
    weight = max(math.prod(w.shape[1:]) * w.dtype.itemsize
                 for w in jax.tree.leaves(params["stage0"]))
    temp = step.compiled.memory_analysis().temp_size_in_bytes
    assert temp < math.prod(layer) * bf16 + weight, temp


def test_zamba2_stage_is_served_in_place_within_one_chip(one_chip):
    """The zamba2-7b-l21.decode cell's served programs (21 layers at
    published widths, hybrid ids 6, 11, 17; batch 64, prompt 512, cache
    1024): the prefill, its chunked scan's temporaries bounded
    (``ssm.scan_rows``), and the decode step each fit one chip beside
    their arguments and results, and the decode step writes the tied
    blocks' 224-wide K/V rows in place, with no copy or slice of a
    layer's cache."""
    cfg = dataclasses.replace(get_config("zamba2-7b"), num_layers=21)
    b, p, g = 64, 512, 512
    opts = RunOptions(chunk_q=32, chunk_kv=32, cache_len=p + g,
                      remat=False, decode_scan=True)
    params = _on(one_chip, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    batch = {"tokens": jax.ShapeDtypeStruct((b, p), jnp.int32,
                                            sharding=one_chip)}
    prefill, step, _ = serve.compile_step_fns(cfg, params, batch, opts, p)
    for program in (prefill, step):
        used = _device_bytes(program.compiled)
        assert 0 < used < HBM_BYTES, used
    a = cfg.attention
    layer = [b, a.num_kv_heads, a.head_dim, p + g]
    assert _moves(_scheduled_ops(step.compiled.as_text()), [layer]) == []


@pytest.mark.parametrize("m,k,n", [(256, 896, 4864), (512, 4864, 896)])
def test_spm_matmul_compiles_for_v5e(one_chip, m, k, n):
    from repro.kernels.spm_matmul.ops import matmul
    a = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda a, b: matmul(a, b, interpret=False)).lower(
        a, b).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    from repro.kernels.flash_attention.ops import attention
    a = get_config("qwen2-0.5b").attention
    S = 2048
    q = jax.ShapeDtypeStruct((1, S, a.num_heads, a.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, a.num_kv_heads, a.head_dim),
                              jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: attention(
        q, k, v, bq=256, bk=256, interpret=False)).lower(q, kv,
                                                         kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
