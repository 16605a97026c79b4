"""Integration correctness: step-by-step decode with a cache must
reproduce the full-forward logits (teacher forcing) — validates cache
semantics for every layer family (GQA, sliding-window, MoE, Mamba2
conv+ssm state, RWKV6 shift+wkv state, enc-dec cross-attn)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from conftest import TINY_LAYERS, tiny_cfg
from repro.models import (compute_logits, decode_step, forward_hidden,
                          init_params, prefill)
from repro.models.lm import RunOptions

ARCHS = ["gemma3-12b", "zamba2-7b", "rwkv6-1.6b", "qwen3-moe-235b-a22b",
         "whisper-base", "qwen2-72b", "qwen2-0.5b", "deepseek-67b",
         "pixtral-12b", "llama4-maverick-400b-a17b"]
B, S, EXTRA = 2, 32, 6


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = tiny_cfg(arch, num_layers=TINY_LAYERS[arch], dtype="float32")
    if cfg.moe:
        # decode routes one token per group and never overflows an
        # expert; the full forward must be dropless too, or the two
        # differ by design (capacity drops), not by a cache bug
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.num_experts / cfg.moe.top_k))
    key = jax.random.PRNGKey(2)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (B, S + EXTRA), 0, cfg.vocab_size)
    bf = {"tokens": toks, "targets": toks}
    bp = {"tokens": toks[:, :S], "targets": toks[:, :S]}
    if cfg.family == "encdec":
        frames = jax.random.normal(key, (B, 32, cfg.d_model), jnp.float32)
        bf["frames"] = bp["frames"] = frames
    opts = RunOptions(chunk_q=8, chunk_kv=8, cache_len=S + EXTRA,
                      remat=False)
    x, _, _ = forward_hidden(cfg, params, bf, opts)
    want = compute_logits(cfg, params, x[:, -1])
    lg, cache = prefill(cfg, params, bp, opts)
    for t in range(EXTRA):
        lg, cache = decode_step(cfg, params, cache, toks[:, S + t],
                                S + t, opts)
    got, want = lg[:, :cfg.vocab_size], want[:, :cfg.vocab_size]
    rel = float(jnp.max(jnp.abs(got - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-9)
    assert rel < 2e-2, (arch, rel)


def test_windowed_ring_cache_matches_full(monkeypatch):
    """wincache variant: sliding-window layers keep an O(window) ring
    buffer; decode must still reproduce the full forward exactly
    (gemma3-style 5:1 local:global pattern)."""
    from repro.configs import get_config
    from repro.models import compute_logits, forward_hidden, init_params
    cfg = get_config("gemma3-12b")
    cfg = dataclasses.replace(
        cfg, num_layers=6, d_model=128, d_ff=256, vocab_size=512,
        vocab_pad_multiple=64, dtype="float32",
        attention=dataclasses.replace(cfg.attention, num_heads=4,
                                      num_kv_heads=2, head_dim=32,
                                      sliding_window=8))
    key = jax.random.PRNGKey(3)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (B, 32 + 10), 0, cfg.vocab_size)
    x, _, _ = forward_hidden(cfg, params, {"tokens": toks},
                             RunOptions(chunk_q=0, chunk_kv=0,
                                        remat=False))
    want = compute_logits(cfg, params, x[:, -1])
    opts = RunOptions(chunk_q=0, chunk_kv=0, cache_len=42, remat=False,
                      windowed_cache=True)
    lg, cache = prefill(cfg, params, {"tokens": toks[:, :32]}, opts)
    assert cache["stage0"]["pos0"]["k"].shape[-1] == 8   # ring!
    assert cache["stage0"]["pos5"]["k"].shape[-1] == 42  # global: full
    for t in range(10):
        lg, cache = decode_step(cfg, params, cache, toks[:, 32 + t],
                                32 + t, opts)
    rel = float(jnp.max(jnp.abs(
        lg[:, :cfg.vocab_size] - want[:, :cfg.vocab_size]))) / float(
        jnp.max(jnp.abs(want[:, :cfg.vocab_size])))
    assert rel < 2e-2, rel
