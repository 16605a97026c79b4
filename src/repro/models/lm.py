"""Unified language model: one entry point for all ten assigned
architectures (dense / sliding-window / MoE / hybrid-SSM / RWKV /
enc-dec / VLM-stub).

Public API
----------
  model_spec(cfg)                      -> Par tree (single source of truth)
  init_params(cfg, key)                -> random params (smoke/examples)
  cache_spec(cfg, batch, cache_len)    -> Par tree for decode state
  init_cache(cfg, batch, cache_len)    -> zero cache
  train_loss(cfg, params, batch, opts) -> scalar loss (fp32)
  prefill(cfg, params, batch, opts)    -> (last_logits [B,V], cache)
  decode_step(cfg, params, cache, token, pos, opts) -> (logits, cache)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import blocks as blk
from repro.models import ffn as ffn_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import rmsnorm, rmsnorm_spec
from repro.models.spec import Par, init_tree, is_par, stack
from repro.obs.blocks import ATTN_CORE, FFN, HEAD

MAX_POS_TABLE = 32_768  # whisper learned-position tables


@dataclass(frozen=True, eq=False)
class RunOptions:
    chunk_q: int = 512
    chunk_kv: int = 512
    loss_chunk: int = 512
    cache_len: int = 0        # prefill: cache buffer length (0 = seq len)
    remat: bool = True
    aux_weight: float = 0.01  # MoE load-balance loss weight
    moe_impl: str = "einsum"  # einsum (GShard baseline) | gather (§Perf)
    windowed_cache: bool = False  # ring-buffer KV for sliding-window
    #                               layers (wincache variant, §Perf)
    # decode-loop structure: scan (one compiled unit body, small
    # program) vs unroll (per-unit programs fused end-to-end).  None =
    # follow cfg.scan_layers; the serving autotuner measures both and
    # pins the winner in the model plan ("decode_scan" 0/1).  Either
    # choice is numerically identical (tests/test_model_plan.py).
    decode_scan: Optional[bool] = None
    # activation sharding constraints (NamedShardings keyed by role);
    # None = single-device / let GSPMD infer.  Keys: "x" (residual
    # stream [B,S,d]), "logits" ([B,C,V]), "kv" (cache [B,S,KV,hd]).
    shardings: Optional[dict] = None


DEFAULT_OPTS = RunOptions()


def _wsc(x: jax.Array, opts: RunOptions, key: str) -> jax.Array:
    """Apply a with_sharding_constraint if configured.

    These constraints are the mesh-scale 'static schedule': they pin the
    activation layout the same way the paper's management core pins
    scratchpad residency, instead of letting the partitioner drift into
    replicated (interference-prone, memory-exploding) layouts."""
    if not opts.shardings:
        return x
    s = opts.shardings.get(key)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


# ---------------------------------------------------------------------------
# parameter / cache specs


def model_spec(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    spec = {
        "embed": Par((cfg.padded_vocab, d), ("vocab", "embed"),
                     init="normal", dtype=cfg.dtype),
        "final_norm": rmsnorm_spec(d),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = Par((cfg.padded_vocab, d), ("vocab", "embed"),
                              init="normal", dtype=cfg.dtype)
    for si, st in enumerate(blk.build_stages(cfg)):
        spec[f"stage{si}"] = blk.stage_spec(cfg, st)
    if cfg.family == "hybrid":
        spec["shared"] = stack(blk.shared_block_spec(cfg),
                               cfg.ssm.n_shared_blocks)
    if cfg.family == "encdec":
        enc = blk.encoder_stage(cfg)
        spec["encoder"] = {
            "stack": blk.stage_spec(cfg, enc),
            "norm": rmsnorm_spec(d),
            "pos": Par((MAX_POS_TABLE, d), (None, "embed"), init="normal",
                       dtype=cfg.dtype),
        }
        spec["dec_pos"] = Par((MAX_POS_TABLE, d), (None, "embed"),
                              init="normal", dtype=cfg.dtype)
    return spec


def init_params(cfg: ModelConfig, key) -> dict:
    return init_tree(model_spec(cfg), key)


def param_count(cfg: ModelConfig) -> int:
    """Total parameter count straight from the spec (no allocation) —
    what the serving WCET model sizes the per-step weight pass with."""
    import numpy as np

    from repro.models.spec import is_par
    return int(sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(model_spec(cfg),
                                            is_leaf=is_par)))


def cache_spec(cfg: ModelConfig, batch: int, cache_len: int,
               windowed: bool = False) -> dict:
    spec = {}
    for si, st in enumerate(blk.build_stages(cfg)):
        spec[f"stage{si}"] = blk.stage_cache_spec(cfg, st, batch,
                                                  cache_len, windowed)
    return spec


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    return init_tree(cache_spec(cfg, batch, cache_len),
                     jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# embedding / logits


def _embed(cfg: ModelConfig, params: dict, tokens: jax.Array,
           batch: Optional[dict] = None,
           opts: RunOptions = DEFAULT_OPTS) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    if (cfg.frontend.kind == "patches" and cfg.frontend.num_positions
            and batch is not None and "patch_embeds" in batch):
        pe = batch["patch_embeds"].astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, pe, (0, 0, 0))
    return _wsc(x, opts, "x")


def _head_table(cfg: ModelConfig, params: dict) -> jax.Array:
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


@jax.named_scope(HEAD)
def compute_logits(cfg: ModelConfig, params: dict,
                   x: jax.Array) -> jax.Array:
    """x: [B, d] -> fp32 logits [B, padded_vocab] (padding masked)."""
    head = _head_table(cfg, params)
    logits = jnp.einsum("bd,vd->bv", x, head,
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    if cfg.padded_vocab != cfg.vocab_size:
        viota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(viota < cfg.vocab_size, logits, -1e30)
    return logits


def lm_loss(cfg: ModelConfig, params: dict, x: jax.Array,
            targets: jax.Array, opts: RunOptions) -> jax.Array:
    """Chunked softmax cross-entropy (fp32 reductions).  x: [B,S,d]."""
    B, S, d = x.shape
    head = _head_table(cfg, params)
    C = opts.loss_chunk if (opts.loss_chunk and S % opts.loss_chunk == 0
                            and S > opts.loss_chunk) else S
    nch = S // C
    xc = jnp.moveaxis(x.reshape(B, nch, C, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, nch, C), 1, 0)

    def body(tot, inp):
        xx, tt = inp
        logits = jnp.einsum("bcd,vd->bcv", xx, head,
                            preferred_element_type=jnp.float32)
        logits = _wsc(logits, opts, "logits")
        if cfg.padded_vocab != cfg.vocab_size:
            viota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
            logits = jnp.where(viota < cfg.vocab_size, logits, -1e30)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, tt[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, tc))
    return tot / (B * S)


# ---------------------------------------------------------------------------
# full-sequence unit application (train / prefill)


@jax.named_scope(ATTN_CORE)
def _to_cache_buf(k: jax.Array, cache_len: int,
                  opts: RunOptions = DEFAULT_OPTS,
                  window: int = 0) -> jax.Array:
    """Prefill's k or v [B, S, KV, hd] as a cache buffer [B, L, KV, hd];
    ``_run_stage_full`` moves the stack to the cache's order."""
    if opts.windowed_cache and window > 0:
        L = min(cache_len, window)
        S = k.shape[1]
        if S > L:
            # ring layout: position p lives in slot p % L; the last L
            # positions cover every slot exactly once (cyclic shift)
            q0 = S - L
            kw = jax.lax.slice_in_dim(k, q0, S, axis=1)
            return _wsc(jnp.roll(kw, q0 % L, axis=1), opts, "kv")
        cache_len = L
    if cache_len <= k.shape[1]:
        return _wsc(k, opts, "kv")
    shape = (k.shape[0], cache_len) + k.shape[2:]
    buf = jax.lax.dynamic_update_slice(
        jnp.zeros(shape, k.dtype), k, (0, 0, 0, 0))
    return _wsc(buf, opts, "kv")


def _hybrid_in(cfg: ModelConfig, sp: dict, x, x0):
    """The tied block's attention input: RMSNorm of concat(x, x0)."""
    return rmsnorm(jnp.concatenate([x, x0], axis=-1), sp["ln_in"],
                   cfg.norm_eps)


def _hybrid_out(cfg: ModelConfig, sp: dict, p: dict, att):
    """The rest of a zamba2 hybrid layer's tied block after attention:
    the gated MLP on the normed attention output, with this layer's
    LoRA on the gate/up projection, then this layer's linear.  The block
    adds no residual: what it returns is added to the layer's Mamba
    input, not to x."""
    g = rmsnorm(att, sp["ln_ffn"], cfg.norm_eps)
    f = ffn_mod.dense_ffn(sp["ffn"], g, cfg.activation,
                          adapter=(p["adapter_a"], p["adapter_b"]))
    with jax.named_scope(FFN):
        return jnp.einsum("bsd,de->bse", f, p["linear"])


def _shared_block(cfg: ModelConfig, stage, shared, unit_idx, i: int):
    """The tied block of the hybrid layer at position ``i`` of unit
    ``unit_idx``: its ordinal among hybrid layers mod the block count."""
    sel = stage.hybrid_ordinal(unit_idx, i) % cfg.ssm.n_shared_blocks
    return blk.tree_index(shared, sel)


def _apply_unit_full(cfg: ModelConfig, up: dict, stage, x, x0, positions,
                     opts: RunOptions, collect: bool, memory, shared,
                     unit_idx, cache_len: int):
    aux = jnp.zeros((), jnp.float32)
    cache = {}
    a = cfg.attention
    for i, dsc in enumerate(stage.unit):
        p = up[f"pos{i}"]
        c = {}
        if dsc.kind in ("attn", "enc_attn"):
            h = rmsnorm(x, p["ln_attn"])
            res = attn_mod.self_attention(
                p["attn"], h, a, positions, theta=dsc.theta,
                window=dsc.window, chunk_q=opts.chunk_q,
                chunk_kv=opts.chunk_kv, causal=dsc.causal,
                return_kv=collect)
            att, kv = res if collect else (res, None)
            if cfg.use_post_norm:
                att = rmsnorm(att, p["ln_attn_post"])
            att = _wsc(att, opts, "x_sp")
            x = x + att
            h = rmsnorm(x, p["ln_ffn"])
            if dsc.use_moe:
                f, al = ffn_mod.moe_ffn(
                    p["moe"], h, cfg.moe, cfg.activation, opts.moe_impl,
                    opts.shardings.get("x") if opts.shardings else None)
                aux = aux + al
            else:
                f = ffn_mod.dense_ffn(p["ffn"], h, cfg.activation)
            if cfg.use_post_norm:
                f = rmsnorm(f, p["ln_ffn_post"])
            f = _wsc(f, opts, "x_sp")
            x = x + f
            if collect:
                c = {"k": _to_cache_buf(kv[0], cache_len, opts,
                                        dsc.window),
                     "v": _to_cache_buf(kv[1], cache_len, opts,
                                        dsc.window)}
        elif dsc.kind == "dec_attn":
            h = rmsnorm(x, p["ln_self"])
            res = attn_mod.self_attention(
                p["self"], h, a, positions, theta=0.0, window=0,
                chunk_q=opts.chunk_q, chunk_kv=opts.chunk_kv,
                return_kv=collect)
            att, kv = res if collect else (res, None)
            x = x + att
            h = rmsnorm(x, p["ln_cross"])
            ck, cv = attn_mod.cross_kv(p["cross"], memory, a)
            x = x + attn_mod.cross_attention(p["cross"], h, ck, cv, a)
            h = rmsnorm(x, p["ln_ffn"])
            x = x + ffn_mod.dense_ffn(p["ffn"], h, cfg.activation)
            if collect:
                c = {"k": _to_cache_buf(kv[0], cache_len, opts),
                     "v": _to_cache_buf(kv[1], cache_len, opts),
                     "ck": ck, "cv": cv}
        elif dsc.kind == "mamba":
            h = x
            if dsc.shared_attn:
                sp = _shared_block(cfg, stage, shared, unit_idx, i)
                res = attn_mod.self_attention(
                    sp["attn"], _hybrid_in(cfg, sp, x, x0), a, positions,
                    theta=a.rope_theta, window=0, chunk_q=opts.chunk_q,
                    chunk_kv=opts.chunk_kv, return_kv=collect)
                att, skv = res if collect else (res, None)
                h = x + _hybrid_out(cfg, sp, p, att)
                if collect:
                    c["shared_k"] = _to_cache_buf(skv[0], cache_len, opts)
                    c["shared_v"] = _to_cache_buf(skv[1], cache_len, opts)
            h = rmsnorm(h, p["ln"], cfg.norm_eps)
            if collect:
                m, st = ssm_mod.mamba_forward(p["mamba"], h, cfg.ssm,
                                              None, return_state=True,
                                              eps=cfg.norm_eps)
                c["conv"], c["ssm"] = st["conv"], st["ssm"]
            else:
                m = ssm_mod.mamba_forward(p["mamba"], h, cfg.ssm,
                                          eps=cfg.norm_eps)
            x = x + m
        elif dsc.kind == "rwkv":
            h = rmsnorm(x, p["ln_tm"])
            if collect:
                tm, st = rwkv_mod.timemix_forward(
                    p["tm"], h, cfg.rwkv, None, return_state=True)
                c["tm"] = st
            else:
                tm = rwkv_mod.timemix_forward(p["tm"], h, cfg.rwkv)
            x = x + tm
            h = rmsnorm(x, p["ln_cm"])
            if collect:
                cm, st2 = rwkv_mod.channelmix_forward(p["cm"], h, None,
                                                      return_state=True)
                c["cm"] = st2
            else:
                cm = rwkv_mod.channelmix_forward(p["cm"], h)
            x = x + cm
        else:
            raise ValueError(dsc.kind)
        if collect:
            cache[f"pos{i}"] = c
    return x, aux, (cache if collect else None)


def _split_kv(cfg: ModelConfig, stage: blk.StageDescr, tree: dict):
    """Split a stage's cache tree into its attention K/V leaves
    (those whose cache spec has a ``kv_seq`` axis) and the rest."""
    spec = blk.stage_cache_spec(cfg, stage, 1, 1)
    kv = {u: {n: l for n, l in c.items()
              if is_par(spec[u][n]) and "kv_seq" in spec[u][n].axes}
          for u, c in tree.items()}
    rest = {u: {n: l for n, l in c.items() if n not in kv[u]}
            for u, c in tree.items()}
    return kv, rest


def _run_stage_full(cfg, sp, stage: blk.StageDescr, x, x0, positions, opts,
                    collect: bool, memory, shared, cache_len: int):
    idxs = jnp.arange(stage.n_units, dtype=jnp.int32)

    def body(carry, inp):
        xx, au = carry
        up, ui = inp
        xx, d_aux, cache = _apply_unit_full(
            cfg, up, stage, xx, x0, positions, opts, collect, memory,
            shared, ui, cache_len)
        return (_wsc(xx, opts, "x"), au + d_aux), cache

    if opts.remat and not collect:
        body = jax.checkpoint(body)

    if cfg.scan_layers:
        (x, aux), caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (sp, idxs))
    else:
        aux = jnp.zeros((), jnp.float32)
        cl = []
        for i in range(stage.n_units):
            (x, aux), ci = body((x, aux),
                                (blk.tree_index(sp, i), jnp.int32(i)))
            cl.append(ci)
        caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *cl)
                  if collect else None)
    if collect:
        # K/V stacks [N, B, L, KV, hd] to the cache's order, sequence
        # last (``blocks.kv_cache_spec``), once and after the loop: moved
        # inside it, each layer's transposed buffer took the on-chip
        # memory the attention's chunks had (a slower prefill)
        kv, rest = _split_kv(cfg, stage, caches)
        caches = {u: {**rest[u], **{n: jnp.moveaxis(c, 2, -1)
                                    for n, c in kv[u].items()}}
                  for u in kv}
    return x, aux, caches


def _encode(cfg: ModelConfig, params: dict, frames: jax.Array,
            opts: RunOptions) -> jax.Array:
    enc = params["encoder"]
    T = frames.shape[1]
    x = frames.astype(jnp.dtype(cfg.dtype)) + enc["pos"][:T]
    positions = jnp.arange(T, dtype=jnp.int32)
    st = blk.encoder_stage(cfg)
    x, _, _ = _run_stage_full(cfg, enc["stack"], st, x, x, positions, opts,
                              False, None, None, 0)
    return rmsnorm(x, enc["norm"])


def forward_hidden(cfg: ModelConfig, params: dict, batch: dict,
                   opts: RunOptions = DEFAULT_OPTS, collect: bool = False,
                   cache_len: int = 0):
    """Run embeddings + all stages.  Returns (x, aux, caches)."""
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens, batch, opts)
    if cfg.family == "encdec":
        S = tokens.shape[1]
        x = x + params["dec_pos"][:S]
        memory = _encode(cfg, params, batch["frames"], opts)
    else:
        memory = None
    x0 = x
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    shared = params.get("shared")
    aux = jnp.zeros((), jnp.float32)
    caches = {}
    for si, st in enumerate(blk.build_stages(cfg)):
        x, a_i, c_i = _run_stage_full(
            cfg, params[f"stage{si}"], st, x, x0, positions, opts, collect,
            memory, shared, cache_len)
        aux = aux + a_i
        caches[f"stage{si}"] = c_i
    with jax.named_scope(HEAD):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux, (caches if collect else None)


# ---------------------------------------------------------------------------
# training


def train_loss(cfg: ModelConfig, params: dict, batch: dict,
               opts: RunOptions = DEFAULT_OPTS) -> jax.Array:
    x, aux, _ = forward_hidden(cfg, params, batch, opts, collect=False)
    loss = lm_loss(cfg, params, x, batch["targets"], opts)
    return loss + opts.aux_weight * aux


# ---------------------------------------------------------------------------
# serving


def prefill(cfg: ModelConfig, params: dict, batch: dict,
            opts: RunOptions = DEFAULT_OPTS):
    """Process the prompt; returns (last-token fp32 logits, cache)."""
    S = batch["tokens"].shape[1]
    cache_len = opts.cache_len or S
    x, _, caches = forward_hidden(cfg, params, batch, opts, collect=True,
                                  cache_len=cache_len)
    logits = compute_logits(cfg, params, x[:, -1])
    return logits, caches


def _apply_unit_decode(cfg: ModelConfig, up: dict, stage, x, x0, pos,
                       opts: RunOptions, cache_unit: dict, shared,
                       unit_idx):
    a = cfg.attention
    new_cache = {}
    for i, dsc in enumerate(stage.unit):
        p = up[f"pos{i}"]
        c = cache_unit[f"pos{i}"]
        nc = {}
        if dsc.kind in ("attn", "enc_attn"):
            h = rmsnorm(x, p["ln_attn"])
            att, nk, nv = attn_mod.decode_attention(
                p["attn"], h, a, c["k"], c["v"], unit_idx, pos,
                theta=dsc.theta, window=dsc.window)
            if cfg.use_post_norm:
                att = rmsnorm(att, p["ln_attn_post"])
            x = x + att
            h = rmsnorm(x, p["ln_ffn"])
            if dsc.use_moe:
                f, _ = ffn_mod.moe_ffn(
                    p["moe"], h, cfg.moe, cfg.activation, opts.moe_impl,
                    opts.shardings.get("x") if opts.shardings else None)
            else:
                f = ffn_mod.dense_ffn(p["ffn"], h, cfg.activation)
            if cfg.use_post_norm:
                f = rmsnorm(f, p["ln_ffn_post"])
            x = x + f
            nc = {"k": nk, "v": nv}
        elif dsc.kind == "dec_attn":
            h = rmsnorm(x, p["ln_self"])
            att, nk, nv = attn_mod.decode_attention(
                p["self"], h, a, c["k"], c["v"], unit_idx, pos, theta=0.0,
                window=0)
            x = x + att
            h = rmsnorm(x, p["ln_cross"])
            x = x + attn_mod.cross_attention(p["cross"], h, c["ck"],
                                             c["cv"], a)
            h = rmsnorm(x, p["ln_ffn"])
            x = x + ffn_mod.dense_ffn(p["ffn"], h, cfg.activation)
            nc = {"k": nk, "v": nv, "ck": c["ck"], "cv": c["cv"]}
        elif dsc.kind == "mamba":
            h = x
            if dsc.shared_attn:
                sp = _shared_block(cfg, stage, shared, unit_idx, i)
                att, sk, sv = attn_mod.decode_attention(
                    sp["attn"], _hybrid_in(cfg, sp, x, x0), a,
                    c["shared_k"], c["shared_v"], unit_idx, pos,
                    theta=a.rope_theta, window=0)
                h = x + _hybrid_out(cfg, sp, p, att)
                nc["shared_k"], nc["shared_v"] = sk, sv
            h = rmsnorm(h, p["ln"], cfg.norm_eps)
            m, st = ssm_mod.mamba_decode(p["mamba"], h, cfg.ssm,
                                         {"conv": c["conv"],
                                          "ssm": c["ssm"]},
                                         eps=cfg.norm_eps)
            x = x + m
            nc["conv"], nc["ssm"] = st["conv"], st["ssm"]
        elif dsc.kind == "rwkv":
            h = rmsnorm(x, p["ln_tm"])
            tm, st = rwkv_mod.timemix_forward(p["tm"], h, cfg.rwkv,
                                              c["tm"], return_state=True)
            x = x + tm
            h = rmsnorm(x, p["ln_cm"])
            cm, st2 = rwkv_mod.channelmix_forward(p["cm"], h, c["cm"],
                                                  return_state=True)
            x = x + cm
            nc = {"tm": st, "cm": st2}
        else:
            raise ValueError(dsc.kind)
        new_cache[f"pos{i}"] = nc
    return x, new_cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                token: jax.Array, pos, opts: RunOptions = DEFAULT_OPTS):
    """One decode step.  token: [B] int32; pos: scalar position of the
    new token.  Returns (fp32 logits [B, padded_vocab], new cache).

    The layer loop carries each stage's stacked attention K/V caches
    whole, and each layer writes only the new token's row into them in
    place (``attention.decode_attention``); the small per-layer states
    (SSM, RWKV, cross-attention memory) go through the loop as per-layer
    inputs and outputs."""
    x = _embed(cfg, params, token[:, None], None, opts)
    if cfg.family == "encdec":
        x = x + jax.lax.dynamic_slice_in_dim(
            params["dec_pos"], jnp.asarray(pos, jnp.int32), 1, axis=0)
    x0 = x
    shared = params.get("shared")
    scan_units = (cfg.scan_layers if opts.decode_scan is None
                  else bool(opts.decode_scan))
    new_caches = {}
    for si, st in enumerate(blk.build_stages(cfg)):
        sp = params[f"stage{si}"]
        idxs = jnp.arange(st.n_units, dtype=jnp.int32)
        kv, rest = _split_kv(cfg, st, cache[f"stage{si}"])

        def body(carry, inp, _st=st):
            xx, kv = carry
            up, ui, cu = inp
            both = {u: {**cu[u], **kv[u]} for u in cu}
            xx, nc = _apply_unit_decode(cfg, up, _st, xx, x0, pos,
                                        opts, both, shared, ui)
            kv, nc = _split_kv(cfg, _st, nc)
            return (xx, kv), nc

        if scan_units:
            (x, kv), rest = jax.lax.scan(body, (x, kv), (sp, idxs, rest))
        else:
            ncl = []
            for i in range(st.n_units):
                (x, kv), ci = body((x, kv), (blk.tree_index(sp, i),
                                             jnp.int32(i),
                                             blk.tree_index(rest, i)))
                ncl.append(ci)
            rest = jax.tree.map(lambda *xs: jnp.stack(xs), *ncl)
        new_caches[f"stage{si}"] = {u: {**rest[u], **kv[u]} for u in kv}
    with jax.named_scope(HEAD):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = compute_logits(cfg, params, x[:, 0])
    return logits, new_caches
