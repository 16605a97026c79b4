"""Shared model pieces: norms, RoPE, activations, embedding helpers."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.spec import Par


# ---------------------------------------------------------------------------
# norms

def rmsnorm_spec(dim: int) -> Par:
    return Par((dim,), (None,), init="ones", dtype="float32")


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# activations

def activate(h_gate: jax.Array, h_up: Optional[jax.Array],
             kind: str) -> jax.Array:
    if kind == "swiglu":
        return jax.nn.silu(h_gate) * h_up
    if kind == "geglu":
        return jax.nn.gelu(h_gate, approximate=True) * h_up
    if kind == "geglu_exact":
        return jax.nn.gelu(h_gate, approximate=False) * h_up
    if kind == "gelu":
        return jax.nn.gelu(h_gate, approximate=True)
    if kind == "relu_sq":
        return jnp.square(jax.nn.relu(h_gate))
    raise ValueError(f"unknown activation {kind}")


def is_gated(kind: str) -> bool:
    return kind in ("swiglu", "geglu", "geglu_exact")


# ---------------------------------------------------------------------------
# rotary embeddings

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)            # [head_dim/2]


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: jax.Array | float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq] int32.

    theta may be a traced scalar (per-layer metadata: gemma3 uses 10k for
    local layers and 1M for global layers with a single code path).
    """
    hd = x.shape[-1]
    exponent = jnp.arange(0, hd, 2, dtype=jnp.float32) / hd
    freqs = 1.0 / (jnp.asarray(theta, jnp.float32) ** exponent)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [...,s,hd/2]
    angles = angles[..., None, :]                               # heads dim
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding / logits

def embed_spec(vocab: int, d_model: int, dtype: str) -> Par:
    return Par((vocab, d_model), ("vocab", "embed"), init="normal",
               dtype=dtype)


def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    # one-hot-free gather; GSPMD turns this into a sharded gather over the
    # vocab-sharded table.
    return jnp.take(table, tokens, axis=0)


def logits_from_embed(table: jax.Array, x: jax.Array) -> jax.Array:
    return jnp.einsum("...d,vd->...v", x, table)


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return jnp.tanh(logits / cap) * cap
    return logits
