"""Plain float32 reference of a dense decoder with grouped-query
attention (Qwen2, LLaMA and DeepSeek-LLM), and its lower-precision
control.

Straight from the published architecture: token embedding; per layer
``x += Wo . softmax(causal(q k^T / sqrt(hd))) v`` on the RMS-normed
input, with rotary embeddings (rotate-half form, base ``rope_theta``) on
q and k, optional q/k/v biases, and kv head ``h // (H / KV)`` serving
query head ``h``; then ``x += W_down (silu(W_gate x) * W_up x)`` on the
RMS-normed result; a final RMS norm; logits against the head (the
embedding when tied).  Every matmul runs at ``Precision.HIGHEST``, so
float32 is float32 on a TPU too.  No cache, no chunking, no batching
beyond a map over sequences.

It runs layer by layer: each layer's weights are made from the seed
(``chipbench.weights``) just before use and dropped after, so the
reference fits on a chip beside nothing else.  It imports nothing of
the program and takes nothing the program made.

The control is the same computation with every matrix (projections,
embedding, head) rounded to a lower precision with one scale per output
channel: ``fp8`` (4 exponent and 3 mantissa bits, as float8 e4m3) or
``int8``, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "embed",
            "lm_head")


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x [T, n, hd] at positions 0..T-1."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def layer(m: dict, w: dict, x):
    """One decoder layer on one sequence x [T, d]."""
    T = x.shape[0]
    H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
    h = rmsnorm(x, w["attn_norm"], m["eps"])
    q, k, v = _mm(h, w["wq"]), _mm(h, w["wk"]), _mm(h, w["wv"])
    if m["qkv_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rope(q.reshape(T, H, hd), m["theta"])
    k = rope(k.reshape(T, KV, hd), m["theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v.reshape(T, KV, hd), H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / hd ** 0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(T, H * hd), w["wo"])
    h = rmsnorm(x, w["ffn_norm"], m["eps"])
    return x + _mm(jax.nn.silu(_mm(h, w["w_gate"])) * _mm(h, w["w_up"]),
                   w["w_down"])


def quantize(w, fmt: str, axis: int):
    """Round ``w`` to ``fmt`` with one absmax scale per output channel
    (the max is taken over ``axis``, the contracted one), then back to
    float32.  fp8 is rounded by ``reduce_precision`` to 4 exponent and 3
    mantissa bits: a float8 round trip by ``astype`` can be removed by
    the TPU compiler as a pair of redundant conversions."""
    top = {"int8": 127.0, "fp8": 240.0}[fmt]
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if fmt == "int8":
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def lower(tree: dict, fmt: str) -> dict:
    """The control's copy of a weight dict: matrices rounded to ``fmt``
    (embedding and head per vocabulary row, projections per output
    column), gains and biases as they are."""
    out = {}
    for name, w in tree.items():
        if name not in MATRICES:
            out[name] = w
        else:
            out[name] = quantize(w, fmt, 1 if name in ("embed", "lm_head")
                                 else 0)
    return out


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _head(m, g, fmt):
    """The head in float32 (or rounded to ``fmt``), one row per token."""
    head = (g["embed"] if m["tied"] else g["lm_head"]).astype(F32)
    return quantize(head, fmt, 1) if fmt else head


def _logits(m, g, head, x):
    return _mm(rmsnorm(x, g["final_norm"], m["eps"]), head.T)


def _gap(ref, pick):
    """Gap of token ``pick`` [n] below the best of ``ref`` [n, V], in
    units of the standard deviation of ``ref``."""
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return (jnp.max(ref, -1) - got) / jnp.std(ref, -1)


class Reference:
    """Compiled pieces of the reference for one configuration; call
    ``gaps`` once per set of sequences.  The embedding and head stay in
    their served dtype on the device and are widened where used."""

    def __init__(self, m: dict):
        self.m = m
        self._layer = jax.jit(lambda l, key: _f32(W.make_layer(m, key, l)))
        self._globals = jax.jit(lambda key: W.make_globals(m, key))
        self._lower = jax.jit(lower, static_argnums=1)
        self._run_layer = jax.jit(
            lambda w, xs: jax.lax.map(functools.partial(layer, m, w), xs))

        def embed(g, toks, fmt):
            rows = jnp.take(g["embed"], toks, 0).astype(F32)
            return quantize(rows, fmt, -1) if fmt else rows

        def served_gaps(g, xs, served):
            head = _head(m, g, None)
            return jax.lax.map(
                lambda a: _gap(_logits(m, g, head, a[0]), a[1]),
                (xs, served))

        def control_gaps(g, xs, xcs, fmt):
            head, low = _head(m, g, None), _head(m, g, fmt)
            return jax.lax.map(
                lambda a: _gap(_logits(m, g, head, a[0]),
                               jnp.argmax(_logits(m, g, low, a[1]), -1)),
                (xs, xcs))

        self._embed = jax.jit(embed, static_argnums=2)
        self._served_gaps = jax.jit(served_gaps)
        self._control_gaps = jax.jit(control_gaps, static_argnums=3)

    def gaps(self, seed: int, seqs, served, controls=()) -> dict:
        """``seqs`` int32 [k, T]: each prompt followed by all but the
        last served token; ``served`` int32 [k, n]: the served tokens,
        predicted at positions T-n..T-1.  Returns, per name, the gaps
        [k, n] in units of the reference logits' standard deviation:
        ``"program"`` for the served tokens, and one per control format
        for the token that control puts first."""
        key = W.base_key(seed)
        n = served.shape[1]
        g = self._globals(key)
        x = self._embed(g, seqs, None)
        xc = {c: self._embed(g, seqs, c) for c in controls}
        for li in range(self.m["layers"]):
            w = self._layer(jnp.uint32(li), key)
            x = self._run_layer(w, x)
            for c in controls:
                xc[c] = self._run_layer(self._lower(w, c), xc[c])
            del w
        out = {"program": self._served_gaps(g, x[:, -n:], served)}
        for c in controls:
            out[c] = self._control_gaps(g, x[:, -n:], xc[c][:, -n:], c)
        return jax.tree.map(jax.device_get, out)
