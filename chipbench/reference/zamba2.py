"""Zamba2 (Zyphra): Mamba2 layers, some of which first run one of a few
weight-tied transformer blocks; the one module of the benchmark that
knows this architecture.  A configuration file names it with
``"reference": "zamba2"``.  Like every architecture's module it exposes
``dims``, ``program_config``, ``make_params``, ``prefill_flops``,
``decode_roofline_s`` and ``Reference`` (``dense_gqa``'s docstring says
what each is), and besides ``ssm_core_bytes``, the yardstick of
``metrics/decode_ssm_core_roofline.py``.

The architecture (``transformers``' ``Zamba2``, arXiv:2411.15242), with
x the residual stream and x0 the token embedding:

- a Mamba2 layer: ``x += out_proj(mamba(rmsnorm(u)))`` with u = x; the
  input projection gives the gate z, the convolved channels (x, B, C)
  and dt; a causal depthwise convolution with bias, then silu; B and C
  come in ``mamba_ngroups`` groups, head h reading group
  h // (heads / groups); per head the recurrence
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = C_t . S_t + D x_t`` with dt = softplus(dt + dt_bias) and
  A = -exp(A_log); then ``rmsnorm(y * silu(z))`` over each group of
  d_inner / groups channels, times a gain;
- a hybrid layer (``hybrid_layer_ids``) first runs tied block
  b = (its ordinal among the hybrid layers) mod ``num_mem_blocks`` on
  concat(x, x0): ``a = Wo . attn(rmsnorm(concat(x, x0)))`` with full
  multi-head attention, rotary embeddings over the whole head (base
  ``rope_theta``) and scale (head_dim / 2) ** -0.5; ``g = rmsnorm(a)``;
  ``[gate, up] = g W_gu + (g A_k) B_k`` with the layer's own rank-
  ``adapter_rank`` adapter; ``t = Lin_k(W_down (gelu(gate) * up))`` with
  exact gelu and the layer's own linear; then its Mamba2 layer runs with
  u = x + t (the residual stays x);
- a final RMS norm and logits against the embedding (tied).

Weights.  Seeded leaves named after the published configuration: per
layer the Mamba2 leaves, per hybrid layer its adapter and linear
besides, and the tied blocks, the embedding and the final norm once.
Projections are uniform with standard deviation 1/sqrt(fan_in) (the
convolution's fan-in is its width), the embedding 0.02, norm gains and
D in 1 +- 1/8, the convolution's bias 0.1.  A in U[1, 16]
(A_log = log A) and dt log-uniform in [time_step_min, time_step_max]
(dt_bias = softplus^-1(dt)), as Mamba2 initialises them, so that the
decays span a few steps to a few hundred.

Counts.  Every matmul of every token, causal attention in the hybrid
layers as each query against the keys at or before it, the recurrence's
state update and read-out (2 H N P multiply-adds a token and layer), and
the head where a token is sampled.  A decode step's bytes: every weight
once (each tied block once, though the program reads it at each of its
hybrid layers), the keys and values of the filled positions of the
hybrid layers and the new ones, the Mamba2 convolution and ssm states
read and written, and the float32 logits.

Reference.  Plain float32 at ``Precision.HIGHEST``, layer by layer with
each layer's weights made from the seed just before use; the Mamba2
recurrence runs as the sequential scan above, one token after another
(not the chunked form the program's prefill uses), so the program's
chunked prefill and its decode update are both checked against it.  It
imports nothing of the program.  The control rounds every matrix (the
projections, convolution weights, adapters, linears, embedding) to
``fp8`` or ``int8`` with ``dense_gqa.quantize``.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from chipbench import model
from chipbench import weights as W
from chipbench.reference import dense_gqa

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BF16 = jnp.bfloat16


# -- sizes --------------------------------------------------------------

def dims(c: dict) -> dict:
    """The sizes the weights and the reference need, from a config file."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    hybrid = tuple(i for i in c["hybrid_layer_ids"]
                   if i < c["num_hidden_layers"])
    kinds = c["layers_block_type"][:c["num_hidden_layers"]]
    if tuple(i for i, k in enumerate(kinds) if k == "hybrid") != hybrid:
        raise ValueError(f"{c['name']}: layers_block_type and "
                         f"hybrid_layer_ids disagree")
    hd = c["attention_head_dim"]
    return {"d": d, "f": c["ffn_hidden_size"],
            "layers": c["num_hidden_layers"], "vocab": c["vocab_size"],
            "d_inner": c["mamba_expand"] * d,
            "ssm_heads": c["n_mamba_heads"], "ssm_head_dim":
            c["mamba_headdim"], "state": c["mamba_d_state"],
            "groups": c["mamba_ngroups"], "conv": c["mamba_d_conv"],
            "chunk": c["chunk_size"], "hybrid": hybrid,
            "blocks": c["num_mem_blocks"], "heads": heads,
            "kv_heads": c["num_key_value_heads"], "head_dim": hd,
            "attn_in": c["attention_hidden_size"],
            "rank": c["adapter_rank"], "theta": float(c["rope_theta"]),
            "scale": (hd / 2) ** -0.5, "eps": float(c["rms_norm_eps"]),
            "tied": bool(c["tie_word_embeddings"]),
            "dt_min": float(c["time_step_min"]),
            "dt_max": float(c["time_step_max"])}


def conv_dim(m: dict) -> int:
    return m["d_inner"] + 2 * m["groups"] * m["state"]


# -- weights ------------------------------------------------------------

def layer_shapes(m: dict) -> dict:
    """name -> (shape, dtype, init, scale) for one Mamba2 layer (A_log
    and dt_bias are made apart, ``make_layer``)."""
    d, di, H, C = m["d"], m["d_inner"], m["ssm_heads"], conv_dim(m)
    span = W.NORM_SPAN
    return {"norm": ((d,), F32, "norm", span),
            "in_proj": ((d, di + C + H), BF16, "normal", d ** -0.5),
            "conv_w": ((m["conv"], C), BF16, "normal", m["conv"] ** -0.5),
            "conv_b": ((C,), BF16, "normal", W.BIAS_STD),
            "D": ((H,), F32, "norm", span),
            "gate_norm": ((di,), F32, "norm", span),
            "out_proj": ((di, d), BF16, "normal", di ** -0.5)}


def hybrid_shapes(m: dict) -> dict:
    """A hybrid layer's own leaves: its adapter and its linear."""
    d, r = m["d"], m["rank"]
    return {"adapter_a": ((d, r), BF16, "normal", d ** -0.5),
            "adapter_b": ((r, 2 * m["f"]), BF16, "normal", r ** -0.5),
            "linear": ((d, d), BF16, "normal", d ** -0.5)}


def block_shapes(m: dict) -> dict:
    """One tied transformer block."""
    d, f, a = m["d"], m["f"], m["attn_in"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    span = W.NORM_SPAN
    return {"ln_in": ((a,), F32, "norm", span),
            "wq": ((a, q), BF16, "normal", a ** -0.5),
            "wk": ((a, kv), BF16, "normal", a ** -0.5),
            "wv": ((a, kv), BF16, "normal", a ** -0.5),
            "wo": ((q, d), BF16, "normal", q ** -0.5),
            "ln_ffn": ((d,), F32, "norm", span),
            "w_gate": ((d, f), BF16, "normal", d ** -0.5),
            "w_up": ((d, f), BF16, "normal", d ** -0.5),
            "w_down": ((f, d), BF16, "normal", f ** -0.5)}


def global_shapes(m: dict) -> dict:
    s = {"embed": ((m["vocab"], m["d"]), BF16, "normal", 0.02),
         "final_norm": ((m["d"],), F32, "norm", W.NORM_SPAN)}
    if not m["tied"]:
        s["lm_head"] = ((m["vocab"], m["d"]), BF16, "normal", 0.02)
    return s


def _unit(key, name: str, shape):
    """Uniform on (0, 1), float32."""
    return W.leaf(key, name, shape, F32, "norm", 1.0) / 2


def make_layer(m: dict, key, layer) -> dict:
    """Layer ``layer``'s Mamba2 leaves in their served dtypes (traceable
    in ``layer``)."""
    k = jax.random.fold_in(key, layer + 1)
    w = W.make(k, layer_shapes(m))
    H = (m["ssm_heads"],)
    w["A_log"] = jnp.log(1.0 + 15.0 * _unit(k, "A", H))
    lo, hi = math.log(m["dt_min"]), math.log(m["dt_max"])
    dt = jnp.exp(lo + (hi - lo) * _unit(k, "dt", H))
    w["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
    return w


def make_hybrid(m: dict, key, layer) -> dict:
    return W.make(jax.random.fold_in(key, layer + 1), hybrid_shapes(m))


def make_block(m: dict, key, b: int) -> dict:
    shapes = {f"block{b}/{n}": s for n, s in block_shapes(m).items()}
    w = W.make(jax.random.fold_in(key, 0), shapes)
    return {n.split("/", 1)[1]: v for n, v in w.items()}


def make_globals(m: dict, key) -> dict:
    return W.make(jax.random.fold_in(key, 0), global_shapes(m))


def weight_bytes(m: dict) -> int:
    """Bytes of every leaf as served."""
    return (m["layers"] * (W.nbytes(layer_shapes(m)) + 2 * 4
                           * m["ssm_heads"])
            + len(m["hybrid"]) * W.nbytes(hybrid_shapes(m))
            + m["blocks"] * W.nbytes(block_shapes(m))
            + W.nbytes(global_shapes(m)))


# -- the program ----------------------------------------------------------

def program_config(c: dict):
    """``repro.configs.get_config`` of the file's ``program.arch`` with
    every size the file states set to the file's value (the fields that
    differ from the registry are under ``program.differs`` in the file,
    with why); raises where the rest of the architecture differs from
    the file's: the family, the gated exact-gelu MLP, the attention's
    form, the dtype."""
    from repro.configs import get_config
    m = dims(c)
    cfg = get_config(c["program"]["arch"])
    ssm = dataclasses.replace(
        cfg.ssm, state_dim=m["state"], head_dim=m["ssm_head_dim"],
        expand=c["mamba_expand"], conv_kernel=m["conv"],
        chunk_size=m["chunk"], n_groups=m["groups"],
        hybrid_layer_ids=tuple(c["hybrid_layer_ids"]),
        n_shared_blocks=m["blocks"], adapter_rank=m["rank"])
    attn = dataclasses.replace(
        cfg.attention, num_heads=m["heads"], num_kv_heads=m["kv_heads"],
        head_dim=m["head_dim"], rope_theta=m["theta"],
        softmax_scale=m["scale"])
    cfg = dataclasses.replace(
        cfg, d_model=m["d"], d_ff=m["f"], num_layers=m["layers"],
        vocab_size=m["vocab"], norm_eps=m["eps"], tie_embeddings=m["tied"],
        attention=attn, ssm=ssm)
    a = cfg.attention
    want = ("hybrid", "geglu_exact", "bfloat16", False, False, 0)
    got = (cfg.family, cfg.activation, cfg.dtype, a.qkv_bias, a.qk_norm,
           a.sliding_window)
    shapes_agree = (m["ssm_heads"] == m["d_inner"] // m["ssm_head_dim"]
                    and m["attn_in"] == 2 * m["d"]
                    and m["heads"] * m["head_dim"] == m["attn_in"]
                    and c["hidden_act"] == "gelu"
                    and c["use_conv_bias"] and not c["add_bias_linear"]
                    and c["use_mem_rope"] and c["use_shared_mlp_adapter"]
                    and not c["use_shared_attention_adapter"])
    if got != want or not shapes_agree:
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{c['name']}: (program, wanted) {got}, {want}; "
                         f"file's shapes as the program builds them: "
                         f"{shapes_agree}")
    return cfg


def _program_layer(w: dict, h: dict | None) -> dict:
    p = {"ln": w["norm"],
         "mamba": {"in_proj": w["in_proj"], "conv_w": w["conv_w"],
                   "conv_b": w["conv_b"], "A_log": w["A_log"],
                   "D": w["D"], "dt_bias": w["dt_bias"],
                   "norm": w["gate_norm"], "out_proj": w["out_proj"]}}
    if h is not None:
        p.update(h)
    return p


def _program_block(m: dict, w: dict) -> dict:
    a, H, KV, hd = m["attn_in"], m["heads"], m["kv_heads"], m["head_dim"]
    return {"ln_in": w["ln_in"],
            "attn": {"wq": w["wq"].reshape(a, H, hd),
                     "wk": w["wk"].reshape(a, KV, hd),
                     "wv": w["wv"].reshape(a, KV, hd),
                     "wo": w["wo"].reshape(H, hd, m["d"])},
            "ln_ffn": w["ln_ffn"],
            "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]}}


def to_program(cfg, m: dict, key) -> dict:
    """The program's parameter tree, every leaf made from ``key``: each
    stage's layers made in one stack, then taken apart by position as
    the program stacks them (``blocks.build_stages``)."""
    from repro.models import blocks as blk
    g = make_globals(m, key)
    p = {"embed": model.pad_vocab(g["embed"], cfg.padded_vocab),
         "final_norm": g["final_norm"]}
    if not m["tied"]:
        p["lm_head"] = model.pad_vocab(g["lm_head"], cfg.padded_vocab)
    start = 0
    for si, st in enumerate(blk.build_stages(cfg)):
        n, k = st.n_units * st.unit_len, st.unit_len
        ids = jnp.arange(start, start + n, dtype=jnp.uint32)
        w = jax.vmap(lambda l: make_layer(m, key, l))(ids)
        stage = {}
        for i, dsc in enumerate(st.unit):
            h = (jax.vmap(lambda l: make_hybrid(m, key, l))(ids[i::k])
                 if dsc.shared_attn else None)
            stage[f"pos{i}"] = _program_layer(
                jax.tree.map(lambda x: x[i::k], w), h)
        p[f"stage{si}"] = stage
        start += n
    blocks = [_program_block(m, make_block(m, key, b))
              for b in range(m["blocks"])]
    p["shared"] = jax.tree.map(lambda *x: jnp.stack(x), *blocks)
    return p


def make_params(cfg, m: dict, seed: int):
    """The program's parameters from ``seed``, made on the device in one
    jitted call, in the dtypes they are served in."""
    return model.seeded_params(cfg, lambda key: to_program(cfg, m, key),
                               seed)


# -- counts -------------------------------------------------------------

def mamba_matmul_params(m: dict) -> int:
    d, di, H = m["d"], m["d_inner"], m["ssm_heads"]
    return d * (di + conv_dim(m) + H) + di * d


def hybrid_matmul_params(m: dict) -> int:
    """A hybrid layer's block (its attention, MLP) with its own adapter
    and linear."""
    d, f, a, r = m["d"], m["f"], m["attn_in"], m["rank"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return a * q + 2 * a * kv + q * d + 3 * d * f + d * r + r * 2 * f \
        + d * d


def _token_flops(m: dict) -> int:
    """One token through every layer's matmuls and recurrence."""
    rec = 4 * m["ssm_heads"] * m["state"] * m["ssm_head_dim"]
    return m["layers"] * (2 * mamba_matmul_params(m) + rec) \
        + len(m["hybrid"]) * 2 * hybrid_matmul_params(m)


def _attn_flops(m: dict, keys: int) -> int:
    return len(m["hybrid"]) * 4 * m["heads"] * m["head_dim"] * keys


def _head_flops(m: dict) -> int:
    return 2 * m["d"] * m["vocab"]


def prefill_flops(m: dict, batch: int, prompt: int) -> int:
    """A prompt of ``prompt`` tokens per row; logits at the last one."""
    return batch * (prompt * _token_flops(m)
                    + _attn_flops(m, prompt * (prompt + 1) // 2)
                    + _head_flops(m))


def decode_flops(m: dict, batch: int, pos: int) -> int:
    return batch * (_token_flops(m) + _attn_flops(m, pos + 1)
                    + _head_flops(m))


def kv_bytes_per_token(m: dict) -> int:
    """bf16 keys and values of one token in every hybrid layer."""
    return len(m["hybrid"]) * 2 * m["kv_heads"] * m["head_dim"] * 2


def state_bytes(m: dict, batch: int) -> int:
    """bf16 convolution and ssm states of every layer."""
    per_row = ((m["conv"] - 1) * conv_dim(m) + m["ssm_heads"] * m["state"]
               * m["ssm_head_dim"]) * 2
    return m["layers"] * batch * per_row


def ssm_core_bytes(m: dict, batch: int) -> int:
    """The states one decode step reads and writes."""
    return 2 * state_bytes(m, batch)


def decode_bytes(m: dict, batch: int, pos: int) -> int:
    kv = kv_bytes_per_token(m)
    return (weight_bytes(m) + batch * pos * kv + batch * kv
            + ssm_core_bytes(m, batch) + batch * m["vocab"] * 4)


def decode_roofline_s(m: dict, peaks: dict, batch: int, pos: int) -> float:
    """The least time one decode step can take on a chip with ``peaks``."""
    return max(decode_flops(m, batch, pos) / peaks["bf16_flops"],
               decode_bytes(m, batch, pos) / peaks["hbm_bytes_per_s"])


# -- the reference --------------------------------------------------------

MATRICES = ("in_proj", "conv_w", "out_proj", "adapter_a", "adapter_b",
            "linear", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def lower(tree: dict, fmt: str) -> dict:
    """The control's copy of a weight dict: every matrix rounded to
    ``fmt`` per output column (``dense_gqa.quantize``)."""
    return {n: dense_gqa.quantize(w, fmt, 0) if n in MATRICES else w
            for n, w in tree.items()}


def mamba(m: dict, w: dict, u):
    """One Mamba2 layer's output on sequences u [k, T, d] (normed)."""
    k, T, _ = u.shape
    di, H, P = m["d_inner"], m["ssm_heads"], m["ssm_head_dim"]
    G, N, K = m["groups"], m["state"], m["conv"]
    zxd = _mm(u, w["in_proj"])
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + conv_dim(m)], \
        zxd[..., -H:]
    xp = jnp.concatenate([jnp.zeros((k, K - 1, xbc.shape[-1]), F32), xbc],
                         1)
    xbc = jax.nn.silu(sum(xp[:, i:i + T] * w["conv_w"][i]
                          for i in range(K)) + w["conv_b"])
    x = xbc[..., :di].reshape(k, T, H, P)
    rep = functools.partial(jnp.repeat, repeats=H // G, axis=2)
    B = rep(xbc[..., di:di + G * N].reshape(k, T, G, N))     # [k,T,H,N]
    C = rep(xbc[..., di + G * N:].reshape(k, T, G, N))
    dt = jax.nn.softplus(dt + w["dt_bias"])                   # [k,T,H]
    A = -jnp.exp(w["A_log"])

    def step(S, t):
        xt, Bt, Ct, dtt = t
        S = jnp.exp(dtt * A)[..., None, None] * S \
            + (dtt[..., None] * Bt)[..., None] * xt[..., None, :]
        return S, jnp.einsum("khn,khnp->khp", Ct, S, precision=HIGHEST)

    first = functools.partial(jnp.moveaxis, source=1, destination=0)
    _, y = jax.lax.scan(step, jnp.zeros((k, H, N, P), F32),
                        (first(x), first(B), first(C), first(dt)))
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x
    y = (y.reshape(k, T, di) * jax.nn.silu(z)).reshape(k, T, G, di // G)
    y = rmsnorm(y, 1.0, m["eps"]).reshape(k, T, di) * w["gate_norm"]
    return _mm(y, w["out_proj"])


def attention(m: dict, b: dict, h):
    """The tied block's attention on one sequence h [T, attn_in]."""
    T = h.shape[0]
    H, hd = m["heads"], m["head_dim"]
    q = dense_gqa.rope(_mm(h, b["wq"]).reshape(T, H, hd), m["theta"])
    kk = dense_gqa.rope(_mm(h, b["wk"]).reshape(T, H, hd), m["theta"])
    v = _mm(h, b["wv"]).reshape(T, H, hd)
    s = jnp.einsum("qhd,khd->hqk", q, kk, precision=HIGHEST) * m["scale"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    return _mm(o.reshape(T, H * hd), b["wo"])


def hybrid(m: dict, b: dict, own: dict, x, x0):
    """What a hybrid layer's tied block adds to its Mamba2 input."""
    h = rmsnorm(jnp.concatenate([x, x0], -1), b["ln_in"], m["eps"])
    g = rmsnorm(jax.lax.map(functools.partial(attention, m, b), h),
                b["ln_ffn"], m["eps"])
    f = m["f"]
    ad = _mm(_mm(g, own["adapter_a"]), own["adapter_b"])
    gate = _mm(g, b["w_gate"]) + ad[..., :f]
    up = _mm(g, b["w_up"]) + ad[..., f:]
    return _mm(_mm(jax.nn.gelu(gate, approximate=False) * up, b["w_down"]),
               own["linear"])


def layer(m: dict, w: dict, x):
    return x + mamba(m, w, rmsnorm(x, w["norm"], m["eps"]))


def hybrid_layer(m: dict, b: dict, own: dict, w: dict, x, x0):
    u = x + hybrid(m, b, own, x, x0)
    return x + mamba(m, w, rmsnorm(u, w["norm"], m["eps"]))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


class Reference:
    """Compiled pieces of the reference for one configuration; call
    ``gaps`` once per set of sequences.  The embedding and head stay in
    their served dtype on the device and are widened where used."""

    def __init__(self, m: dict):
        self.m = m
        self._layer = jax.jit(lambda l, key: _f32(make_layer(m, key, l)))
        self._hybrid = jax.jit(lambda l, key: _f32(make_hybrid(m, key, l)))
        self._block = jax.jit(lambda key, b: _f32(make_block(m, key, b)),
                              static_argnums=1)
        self._globals = jax.jit(lambda key: make_globals(m, key))
        self._lower = jax.jit(lower, static_argnums=1)
        self._run = jax.jit(functools.partial(layer, m))
        self._run_hybrid = jax.jit(functools.partial(hybrid_layer, m))

        def head(g, fmt):
            t = (g["embed"] if m["tied"] else g["lm_head"]).astype(F32)
            return dense_gqa.quantize(t, fmt, 1) if fmt else t

        def logits(g, t, x):
            return _mm(rmsnorm(x, g["final_norm"], m["eps"]), t.T)

        def embed(g, toks, fmt):
            rows = jnp.take(g["embed"], toks, 0).astype(F32)
            return dense_gqa.quantize(rows, fmt, -1) if fmt else rows

        def served_gaps(g, xs, served):
            t = head(g, None)
            return jax.lax.map(
                lambda a: dense_gqa._gap(logits(g, t, a[0]), a[1]),
                (xs, served))

        def control_gaps(g, xs, xcs, fmt):
            t, low = head(g, None), head(g, fmt)
            return jax.lax.map(
                lambda a: dense_gqa._gap(
                    logits(g, t, a[0]),
                    jnp.argmax(logits(g, low, a[1]), -1)),
                (xs, xcs))

        self._embed = jax.jit(embed, static_argnums=2)
        self._logits = jax.jit(lambda g, x: logits(g, head(g, None), x))
        self._served_gaps = jax.jit(served_gaps)
        self._control_gaps = jax.jit(control_gaps, static_argnums=3)

    def gaps(self, seed: int, seqs, served, controls=()) -> dict:
        """As ``dense_gqa.Reference.gaps``: per name, the gaps [k, n] of
        ``"program"``'s served tokens and of each control's first
        choice, in units of the reference logits' standard deviation."""
        n = served.shape[1]
        g, x, xc = self._forward(seed, seqs, controls)
        out = {"program": self._served_gaps(g, x[:, -n:], served)}
        for c in controls:
            out[c] = self._control_gaps(g, x[:, -n:], xc[c][:, -n:], c)
        return jax.tree.map(jax.device_get, out)

    def logits(self, seed: int, seqs):
        """The reference's float32 logits [k, T, vocab] at every
        position of ``seqs`` int32 [k, T]."""
        g, x, _ = self._forward(seed, seqs, ())
        return self._logits(g, x)

    def _forward(self, seed: int, seqs, controls):
        """The globals, and the last layer's output of the reference and
        of each control."""
        m, key = self.m, W.base_key(seed)
        g = self._globals(key)
        x = self._embed(g, seqs, None)
        xc = {c: self._embed(g, seqs, c) for c in controls}
        x0, x0c = x, dict(xc)
        blocks = {}
        for li in range(m["layers"]):
            w = self._layer(jnp.uint32(li), key)
            if li in m["hybrid"]:
                b = m["hybrid"].index(li) % m["blocks"]
                if b not in blocks:
                    blocks[b] = self._block(key, b)
                own = self._hybrid(jnp.uint32(li), key)
                x = self._run_hybrid(blocks[b], own, w, x, x0)
                for c in controls:
                    xc[c] = self._run_hybrid(
                        self._lower(blocks[b], c), self._lower(own, c),
                        self._lower(w, c), xc[c], x0c[c])
            else:
                x = self._run(w, x)
                for c in controls:
                    xc[c] = self._run(self._lower(w, c), xc[c])
            del w
        return g, x, xc
