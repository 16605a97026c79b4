import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Hermeticity: never let the suite read (or write) a developer's real
# tuning plan cache — kernel wrappers would silently pick up tuned
# block plans and change what the conformance cases execute.
# tests/test_tuning.py re-enables autotuning per-test with a tmp cache.
os.environ.setdefault("REPRO_AUTOTUNE", "0")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# The suite runs on the CPU only, even where a TPU is attached: a test
# process that took the chip would hold it for its whole life.
jax.config.update("jax_platforms", "cpu")

from repro.configs import get_config  # noqa: E402
from repro.models.lm import RunOptions  # noqa: E402

TINY_OPTS = RunOptions(chunk_q=16, chunk_kv=16, loss_chunk=16, remat=False)

# Shared kernel tolerance policy: one place decides how close a Pallas
# kernel must track its ref.py oracle (relative max-abs error, scaled
# by the oracle's magnitude).  Used by tests/kernel_conformance.py for
# every registered kernel; per-case overrides exist only for kernels
# whose oracle accumulates in a different order (see kernels/__init__).
KERNEL_TOLERANCES = {
    "float32": 1e-5,
    "bfloat16": 3e-2,
}


def assert_kernel_close(got, want, dtype: str, tol: float = None):
    tol = tol if tol is not None else KERNEL_TOLERANCES[dtype]
    got_leaves = jax.tree.leaves(got)
    want_leaves = jax.tree.leaves(want)
    assert len(got_leaves) == len(want_leaves), \
        (len(got_leaves), len(want_leaves))
    for g, w in zip(got_leaves, want_leaves):
        g = jnp.asarray(g, jnp.float32)
        w = jnp.asarray(w, jnp.float32)
        assert g.shape == w.shape, (g.shape, w.shape)
        scale = float(jnp.max(jnp.abs(w))) + 1e-9
        err = float(jnp.max(jnp.abs(g - w))) / scale
        assert err < tol, f"rel err {err:.2e} >= {tol:.0e} ({dtype})"


def tiny_cfg(name: str, **kw):
    """Reduced-config instance of an assigned architecture (same family,
    small dims) — used by the per-arch smoke tests."""
    cfg = get_config(name)
    base = dict(d_model=128, d_ff=256, vocab_size=512,
                vocab_pad_multiple=64)
    if cfg.attention:
        base["attention"] = dataclasses.replace(
            cfg.attention, num_heads=4, num_kv_heads=2, head_dim=32)
    if cfg.moe:
        base["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, top_k=min(cfg.moe.top_k, 2),
            expert_ff=64, group_size=32, capacity_factor=2.0,
            shared_expert_ff=(64 if cfg.moe.shared_expert_ff else 0))
    if cfg.ssm:
        base["ssm"] = dataclasses.replace(cfg.ssm, chunk_size=16)
        base["attention"] = dataclasses.replace(
            cfg.attention, num_heads=4, num_kv_heads=4, head_dim=64)
    if cfg.rwkv:
        base["rwkv"] = dataclasses.replace(cfg.rwkv, head_dim=32,
                                           chunk_size=16)
    if cfg.encdec:
        base["encdec"] = dataclasses.replace(
            cfg.encdec, encoder_layers=2, cross_kv_len=32)
    base.update(kw)
    return dataclasses.replace(cfg, **base)


TINY_LAYERS = {
    "gemma3-12b": 6,            # one 5:1 local:global pattern unit
    "qwen2-0.5b": 2,
    "deepseek-67b": 2,
    "qwen2-72b": 2,
    "pixtral-12b": 2,
    "whisper-base": 2,
    "zamba2-7b": 23,            # [6 M], [H, 4 M], 2 x [H, 5 M]: hybrid
    #                             ids 6, 11, 17; blocks 0, 1, 0
    "llama4-maverick-400b-a17b": 4,
    "qwen3-moe-235b-a22b": 2,
    "rwkv6-1.6b": 2,
}
