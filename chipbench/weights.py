"""Seeded random weights of a dense GQA decoder, in the benchmark's own
layout.

The leaves are named and shaped after the published configuration (the
keys of ``chipbench/configs/<config>.json``), not after the program's
parameter tree: the plain reference reads them as they are, and
``model.program_params`` maps them onto the program's tree.  Every leaf
of layer ``l`` comes from ``fold_in(fold_in(key, l + 1), leaf_id)``, so
one layer can be made on its own (the reference, layer by layer) and
gives the same numbers as the vmapped whole (the program, in one call).

Values are uniform, with the standard deviations of the program's
initialisers (``models/spec.py``): 0.02 for the embedding and head,
1/sqrt(fan_in) for a projection.  The leaves the program starts at 0 or
1 (biases, norm gains) are spread around that value, so that the
comparison sees whether they are used.  Each value is made from random
bits by exact steps and one rounding, so that two programs that fuse the
arithmetic differently still make the same weights (a Gaussian's
polynomial does not promise that: one bfloat16 value in 44,032 came out
one unit apart).

This module imports nothing of the program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.1
NORM_SPAN = 0.125    # gains uniform in 1 +- NORM_SPAN (a power of two)


def dims(c: dict) -> dict:
    """The sizes the weights and the reference need, from a config file."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "f": c["intermediate_size"],
            "layers": c["num_hidden_layers"], "heads": heads,
            "kv_heads": c["num_key_value_heads"], "head_dim": d // heads,
            "vocab": c["vocab_size"], "theta": float(c["rope_theta"]),
            "eps": float(c["rms_norm_eps"]),
            "tied": bool(c["tie_word_embeddings"]),
            "qkv_bias": bool(c["qkv_bias"])}


def base_key(seed: int) -> jax.Array:
    """A legacy threefry key from all 64 bits of ``seed`` (``PRNGKey``
    keeps only the low 32)."""
    s = int(seed) % (1 << 64)
    return jnp.array([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32)


def layer_shapes(m: dict) -> dict:
    """name -> (shape, dtype, init, scale) for one decoder layer."""
    d, f = m["d"], m["f"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    bf = jnp.bfloat16
    s = {"attn_norm": ((d,), jnp.float32, "norm", NORM_SPAN),
         "wq": ((d, q), bf, "normal", d ** -0.5),
         "wk": ((d, kv), bf, "normal", d ** -0.5),
         "wv": ((d, kv), bf, "normal", d ** -0.5),
         "wo": ((q, d), bf, "normal", q ** -0.5),
         "ffn_norm": ((d,), jnp.float32, "norm", NORM_SPAN),
         "w_gate": ((d, f), bf, "normal", d ** -0.5),
         "w_up": ((d, f), bf, "normal", d ** -0.5),
         "w_down": ((f, d), bf, "normal", f ** -0.5)}
    if m["qkv_bias"]:
        s.update(bq=((q,), bf, "normal", BIAS_STD),
                 bk=((kv,), bf, "normal", BIAS_STD),
                 bv=((kv,), bf, "normal", BIAS_STD))
    return s


def global_shapes(m: dict) -> dict:
    s = {"embed": ((m["vocab"], m["d"]), jnp.bfloat16, "normal", 0.02),
         "final_norm": ((m["d"],), jnp.float32, "norm", NORM_SPAN)}
    if not m["tied"]:
        s["lm_head"] = ((m["vocab"], m["d"]), jnp.bfloat16, "normal", 0.02)
    return s


def _leaf(key, name: str, shape, dtype, init: str, scale: float):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    bits = jax.random.bits(k, shape, jnp.uint32) >> 9
    # (2i + 1) / 2**23 - 1, exact in float32: uniform on (-1, 1)
    u = (bits.astype(jnp.float32) * 2.0 + 1.0) * 2.0 ** -23 - 1.0
    if init == "norm":
        return (1.0 + u * scale).astype(dtype)
    return (u * (scale * 3.0 ** 0.5)).astype(dtype)


def make_layer(m: dict, key, layer) -> dict:
    """Layer ``layer``'s leaves in their served dtypes (traceable in
    ``layer``)."""
    lk = jax.random.fold_in(key, layer + 1)
    return {n: _leaf(lk, n, *spec) for n, spec in layer_shapes(m).items()}


def make_globals(m: dict, key) -> dict:
    gk = jax.random.fold_in(key, 0)
    return {n: _leaf(gk, n, *spec) for n, spec in global_shapes(m).items()}


def make_all(m: dict, key) -> dict:
    """Every leaf; layers stacked on a leading axis (one program)."""
    layers = jax.vmap(lambda l: make_layer(m, key, l))(
        jnp.arange(m["layers"], dtype=jnp.uint32))
    return {"globals": make_globals(m, key), "layers": layers}


def weight_bytes(m: dict) -> int:
    """Bytes of every leaf as served."""
    def nbytes(shapes):
        return sum(int(np.prod(s)) * jnp.dtype(t).itemsize
                   for s, t, _, _ in shapes.values())
    return m["layers"] * nbytes(layer_shapes(m)) + nbytes(global_shapes(m))
