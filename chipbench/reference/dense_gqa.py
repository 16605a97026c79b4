"""A dense decoder with grouped-query attention (Qwen2, LLaMA and
DeepSeek-LLM): the one module of the benchmark that knows this
architecture.  A configuration file names it with
``"reference": "dense_gqa"``, and the harness finds it by that name
(``catalog.architecture``).  Like every architecture's module it
exposes:

- ``dims(c)``: the sizes of the configuration file ``c`` (at least
  ``d``, ``layers`` and ``vocab``);
- ``program_config(c)``: the program's ``ModelConfig``;
- ``make_params(cfg, m, seed)``: the program's parameters from the seed;
- ``prefill_flops(m, batch, prompt)`` and
  ``decode_roofline_s(m, peaks, batch, pos)``: the yardstick of the
  ``*_mfu`` metrics;
- ``Reference(m)``, whose ``gaps`` decides ``correct``.

Weights.  Seeded leaves named and shaped after the published
configuration (``weights``): per layer the norms and projections, and
the embedding, final norm and untied head; ``to_program`` maps them
onto the program's tree.

Counts.  Every matmul of every token (2 operations per multiply-add),
causal attention as each query against the keys at or before it, and
the head only where a token is sampled.  Not counted: norms, rotary
embeddings, softmax, activations, masking and any work a program does
beyond this (attention over masked or empty slots).  So a program's
time can never be shorter than these counts at the chip's peaks.

Reference.  Plain float32, straight from the published architecture:
token embedding; per layer ``x += Wo . softmax(causal(q k^T / sqrt(hd))) v``
on the RMS-normed input, with rotary embeddings (rotate-half form, base
``rope_theta``) on q and k, optional q/k/v biases, and kv head
``h // (H / KV)`` serving query head ``h``; then
``x += W_down (silu(W_gate x) * W_up x)`` on the RMS-normed result; a
final RMS norm; logits against the head (the embedding when tied).
Every matmul runs at ``Precision.HIGHEST``, so float32 is float32 on a
TPU too.  No cache, no chunking, no batching beyond a map over
sequences.  It runs layer by layer: each layer's weights are made from
the seed just before use and dropped after, so the reference fits on a
chip beside nothing else.  It imports nothing of the program and takes
nothing the program made (only ``program_config`` and ``make_params``
import the program).

The control is the same computation with every matrix (projections,
embedding, head) rounded to a lower precision with one scale per output
channel: ``fp8`` (4 exponent and 3 mantissa bits, as float8 e4m3) or
``int8``, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench import model
from chipbench import weights as W


# -- sizes --------------------------------------------------------------

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


# -- weights ------------------------------------------------------------

def layer_shapes(m: dict) -> dict:
    """name -> (shape, dtype, init, scale) for one decoder layer."""
    d, f = m["d"], m["f"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    bf, span = jnp.bfloat16, W.NORM_SPAN
    s = {"attn_norm": ((d,), jnp.float32, "norm", span),
         "wq": ((d, q), bf, "normal", d ** -0.5),
         "wk": ((d, kv), bf, "normal", d ** -0.5),
         "wv": ((d, kv), bf, "normal", d ** -0.5),
         "wo": ((q, d), bf, "normal", q ** -0.5),
         "ffn_norm": ((d,), jnp.float32, "norm", span),
         "w_gate": ((d, f), bf, "normal", d ** -0.5),
         "w_up": ((d, f), bf, "normal", d ** -0.5),
         "w_down": ((f, d), bf, "normal", f ** -0.5)}
    if m["qkv_bias"]:
        s.update(bq=((q,), bf, "normal", W.BIAS_STD),
                 bk=((kv,), bf, "normal", W.BIAS_STD),
                 bv=((kv,), bf, "normal", W.BIAS_STD))
    return s


def global_shapes(m: dict) -> dict:
    s = {"embed": ((m["vocab"], m["d"]), jnp.bfloat16, "normal", 0.02),
         "final_norm": ((m["d"],), jnp.float32, "norm", W.NORM_SPAN)}
    if not m["tied"]:
        s["lm_head"] = ((m["vocab"], m["d"]), jnp.bfloat16, "normal", 0.02)
    return s


def make_layer(m: dict, key, layer) -> dict:
    """Layer ``layer``'s leaves in their served dtypes (traceable in
    ``layer``)."""
    return W.make(jax.random.fold_in(key, layer + 1), layer_shapes(m))


def make_globals(m: dict, key) -> dict:
    return W.make(jax.random.fold_in(key, 0), global_shapes(m))


def make_all(m: dict, key) -> dict:
    """Every leaf; layers stacked on a leading axis (one program)."""
    layers = jax.vmap(lambda l: make_layer(m, key, l))(
        jnp.arange(m["layers"], dtype=jnp.uint32))
    return {"globals": make_globals(m, key), "layers": layers}


def weight_bytes(m: dict) -> int:
    """Bytes of every leaf as served."""
    return m["layers"] * W.nbytes(layer_shapes(m)) + \
        W.nbytes(global_shapes(m))


# -- the program ----------------------------------------------------------

# published config key -> the program's ModelConfig field
_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
           "tie_word_embeddings": "tie_embeddings"}
_ATTN = {"num_attention_heads": "num_heads",
         "num_key_value_heads": "num_kv_heads", "rope_theta": "rope_theta",
         "qkv_bias": "qkv_bias"}


def program_config(c: dict):
    """``repro.configs.get_config`` of the file's ``program.arch``, with
    every field the file states (the published keys of ``_FIELDS`` and
    ``_ATTN``, and the head size they imply) set to the file's value;
    raises where the rest of the architecture differs from the file's.
    The fields that differ from the registry are under
    ``program.differs`` in the file, with why."""
    from repro.configs import get_config
    cfg = get_config(c["program"]["arch"])
    cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(
            cfg.attention, head_dim=dims(c)["head_dim"],
            **{f: c[k] for k, f in _ATTN.items()}),
        **{f: c[k] for k, f in _FIELDS.items()})
    a = cfg.attention
    bad = {}
    if cfg.family != "dense" or cfg.activation != "swiglu" \
            or a.sliding_window or a.qk_norm or cfg.dtype != "bfloat16":
        bad["architecture"] = (cfg.family, cfg.activation,
                               a.sliding_window, a.qk_norm, cfg.dtype)
    if bad:
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{c['name']}: (program, file) {bad}")
    return cfg


def to_program(cfg, m: dict, w: dict) -> dict:
    """Map the benchmark's leaves onto the program's parameter tree."""
    g, L = w["globals"], w["layers"]
    n, H, KV, hd = m["layers"], m["heads"], m["kv_heads"], m["head_dim"]
    attn = {"wq": L["wq"].reshape(n, m["d"], H, hd),
            "wk": L["wk"].reshape(n, m["d"], KV, hd),
            "wv": L["wv"].reshape(n, m["d"], KV, hd),
            "wo": L["wo"].reshape(n, H, hd, m["d"])}
    if m["qkv_bias"]:
        attn.update(bq=L["bq"].reshape(n, H, hd),
                    bk=L["bk"].reshape(n, KV, hd),
                    bv=L["bv"].reshape(n, KV, hd))
    p = {"embed": model.pad_vocab(g["embed"], cfg.padded_vocab),
         "final_norm": g["final_norm"],
         "stage0": {"pos0": {
             "ln_attn": L["attn_norm"], "attn": attn,
             "ln_ffn": L["ffn_norm"],
             "ffn": {"w_gate": L["w_gate"], "w_up": L["w_up"],
                     "w_down": L["w_down"]}}}}
    if not m["tied"]:
        p["lm_head"] = model.pad_vocab(g["lm_head"], cfg.padded_vocab)
    return p


def make_params(cfg, m: dict, seed: int):
    """The program's parameters from ``seed``, made on the device in one
    jitted call, in the dtypes they are served in."""
    return model.seeded_params(
        cfg, lambda key: to_program(cfg, m, make_all(m, key)), seed)


# -- counts -------------------------------------------------------------

def layer_matmul_params(m: dict) -> int:
    d, f = m["d"], m["f"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def _attn_flops(m: dict, keys: int) -> int:
    """One query against ``keys`` keys in every layer: q.k and p.v."""
    return m["layers"] * 4 * m["heads"] * m["head_dim"] * keys


def _head_flops(m: dict) -> int:
    return 2 * m["d"] * m["vocab"]


def prefill_flops(m: dict, batch: int, prompt: int) -> int:
    """A prompt of ``prompt`` tokens per row; logits at the last one."""
    per_row = (prompt * m["layers"] * 2 * layer_matmul_params(m)
               + _attn_flops(m, prompt * (prompt + 1) // 2)
               + _head_flops(m))
    return batch * per_row


def decode_flops(m: dict, batch: int, pos: int) -> int:
    """One decode step writing position ``pos`` (it attends to pos + 1
    keys) for ``batch`` rows."""
    return batch * (m["layers"] * 2 * layer_matmul_params(m)
                    + _attn_flops(m, pos + 1) + _head_flops(m))


def kv_bytes_per_token(m: dict) -> int:
    """bf16 keys and values of one token in every layer."""
    return m["layers"] * 2 * m["kv_heads"] * m["head_dim"] * 2


def weight_read_bytes(m: dict, batch: int) -> int:
    """Weights one step reads: every layer once (bf16 matrices and
    biases, f32 gains), the head once, and the embedding rows of the
    batch where the head is not the embedding."""
    d, n = m["d"], m["layers"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    layer = 2 * layer_matmul_params(m) + 4 * 2 * d
    if m["qkv_bias"]:
        layer += 2 * (q + 2 * kv)
    head = 2 * m["vocab"] * d + 4 * d
    rows = 0 if m["tied"] else 2 * batch * d
    return n * layer + head + rows


def decode_bytes(m: dict, batch: int, pos: int) -> int:
    """One decode step at position ``pos``: the weights once, the keys
    and values of the ``pos`` filled positions, the new token's keys and
    values, and the float32 logits."""
    kv = kv_bytes_per_token(m)
    return (weight_read_bytes(m, batch) + batch * pos * kv + batch * kv
            + batch * m["vocab"] * 4)


def decode_roofline_s(m: dict, peaks: dict, batch: int, pos: int) -> float:
    """The least time one decode step can take on a chip with ``peaks``."""
    return max(decode_flops(m, batch, pos) / peaks["bf16_flops"],
               decode_bytes(m, batch, pos) / peaks["hbm_bytes_per_s"])


# -- the reference --------------------------------------------------------

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
        self._layer = jax.jit(lambda l, key: _f32(make_layer(m, key, l)))
        self._globals = jax.jit(lambda key: make_globals(m, key))
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
