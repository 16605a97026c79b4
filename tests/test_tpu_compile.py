"""The TPU compiler's verdict on the served path, without a chip.

Compiles qwen2-0.5b's prefill and donated decode step at published
widths (the shapes ``chip_smoke.py`` serves) and the Pallas kernels at
qwen2-0.5b's projection and attention shapes, for one chip of a
*described* v5e:2x2 topology.  Nothing runs, so this says nothing about
results or times; it catches what interpret mode cannot: VMEM
overflows, unaligned tiles, programs that do not fit in HBM.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports every test module.  Keep these compiles in this one file.
``wkv6`` is absent on purpose: its kernel does not lower for the chip
(see ROADMAP design debts).
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.configs import get_config
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
