"""Layer-stack assembly.

A model is a sequence of *stages*; each stage scans over ``n_units``
repeat units; a unit is a fixed tuple of layer descriptors (positions)
unrolled inside the scan body.  This gives one traced program per stage
regardless of depth (compile-time friendly at 512 devices) while
supporting heterogeneous patterns:

  gemma3    : 1 stage, 8 units  x [L,L,L,L,L,G] attention layers
  llama4    : 1 stage, 24 units x [moe_layer, dense_layer]
  zamba2    : the layers grouped at its hybrid ids (6, 11, 17, ..., 77):
              6 units x [mamba], 1 x [hybrid, mamba x4],
              11 x [hybrid, mamba x5], 1 x [hybrid, mamba x3]
  others    : 1 stage, n_layers units x [layer]

Static per-position metadata (window size, rope theta, moe flag) is
baked into the traced program; per-unit dynamic metadata (the unit
index, which also picks zamba2's alternating tied block in a stage of
several hybrid units) is scanned over.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import ffn as ffn_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import rmsnorm, rmsnorm_spec
from repro.models.spec import Par, stack


# ---------------------------------------------------------------------------
# descriptors


@dataclass(frozen=True)
class LayerDescr:
    kind: str                  # attn | mamba | rwkv | enc_attn | dec_attn
    window: int = 0            # 0 = global
    theta: float = 10_000.0
    use_moe: bool = False
    shared_attn: bool = False  # zamba2 hybrid layer: tied block first
    causal: bool = True


@dataclass(frozen=True)
class StageDescr:
    n_units: int
    unit: Tuple[LayerDescr, ...]
    hybrid0: int = 0   # ordinal among hybrid layers of the stage's first

    @property
    def unit_len(self) -> int:
        return len(self.unit)

    def hybrid_ordinal(self, unit_idx, i: int):
        """Ordinal among the model's hybrid layers of the hybrid layer
        at position ``i`` of unit ``unit_idx`` (static where the stage
        has one unit, else traced like ``unit_idx``)."""
        per = sum(d.shared_attn for d in self.unit)
        before = sum(d.shared_attn for d in self.unit[:i])
        if self.n_units == 1:
            return self.hybrid0 + before
        return self.hybrid0 + unit_idx * per + before


def _hybrid_stages(cfg: ModelConfig) -> Tuple[StageDescr, ...]:
    """The Mamba layers before the first hybrid id as units of one
    layer, then one unit per hybrid id: [hybrid, mamba x k] up to the
    next id; neighbouring units of equal k share a stage.  Ids at or
    past ``num_layers`` lie beyond this model's layers (a stage of a
    deeper model)."""
    ids = [i for i in cfg.ssm.hybrid_layer_ids if i < cfg.num_layers]
    mamba, hybrid = LayerDescr("mamba"), LayerDescr("mamba",
                                                    shared_attn=True)
    lead = ids[0] if ids else cfg.num_layers
    stages = [StageDescr(lead, (mamba,))] if lead else []
    for k, start in enumerate(ids):
        end = ids[k + 1] if k + 1 < len(ids) else cfg.num_layers
        unit = (hybrid,) + (mamba,) * (end - start - 1)
        last = stages[-1] if stages else None
        if last is not None and last.unit == unit:
            stages[-1] = StageDescr(last.n_units + 1, unit, last.hybrid0)
        else:
            stages.append(StageDescr(1, unit, k))
    return tuple(stages)


def build_stages(cfg: ModelConfig) -> Tuple[StageDescr, ...]:
    a = cfg.attention
    if cfg.family in ("dense", "vlm"):
        if a.layer_pattern:
            unit = tuple(
                LayerDescr("attn",
                           window=a.window_for_layer(i),
                           theta=(a.rope_theta_global or a.rope_theta)
                           if a.window_for_layer(i) == 0 else a.rope_theta)
                for i in range(len(a.layer_pattern)))
            return (StageDescr(cfg.num_layers // len(unit), unit),)
        unit = (LayerDescr("attn", theta=a.rope_theta),)
        return (StageDescr(cfg.num_layers, unit),)

    if cfg.family == "moe":
        m = cfg.moe
        if m.moe_every == 1:
            unit = (LayerDescr("attn", theta=a.rope_theta, use_moe=True),)
            return (StageDescr(cfg.num_layers, unit),)
        unit = tuple(
            LayerDescr("attn", theta=a.rope_theta,
                       use_moe=(i % m.moe_every == 0))
            for i in range(m.moe_every))
        return (StageDescr(cfg.num_layers // m.moe_every, unit),)

    if cfg.family == "hybrid":
        return _hybrid_stages(cfg)

    if cfg.family == "rwkv":
        return (StageDescr(cfg.num_layers, (LayerDescr("rwkv"),)),)

    if cfg.family == "encdec":
        unit = (LayerDescr("dec_attn", theta=0.0),)
        return (StageDescr(cfg.num_layers, unit),)

    raise ValueError(cfg.family)


def encoder_stage(cfg: ModelConfig) -> StageDescr:
    assert cfg.family == "encdec"
    return StageDescr(cfg.encdec.encoder_layers,
                      (LayerDescr("enc_attn", theta=0.0, causal=False),))


# ---------------------------------------------------------------------------
# per-layer parameter specs


def layer_spec(cfg: ModelConfig, dsc: LayerDescr) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    if dsc.kind in ("attn", "enc_attn"):
        p = {
            "ln_attn": rmsnorm_spec(d),
            "attn": attn_mod.attn_spec(d, cfg.attention, dt),
            "ln_ffn": rmsnorm_spec(d),
        }
        if dsc.use_moe:
            p["moe"] = ffn_mod.moe_spec(d, cfg.moe, cfg.activation, dt)
        else:
            p["ffn"] = ffn_mod.dense_ffn_spec(d, cfg.d_ff, cfg.activation,
                                              dt)
        if cfg.use_post_norm:
            p["ln_attn_post"] = rmsnorm_spec(d)
            p["ln_ffn_post"] = rmsnorm_spec(d)
        return p
    if dsc.kind == "dec_attn":
        return {
            "ln_self": rmsnorm_spec(d),
            "self": attn_mod.attn_spec(d, cfg.attention, dt),
            "ln_cross": rmsnorm_spec(d),
            "cross": attn_mod.attn_spec(d, cfg.attention, dt),
            "ln_ffn": rmsnorm_spec(d),
            "ffn": ffn_mod.dense_ffn_spec(d, cfg.d_ff, cfg.activation, dt),
        }
    if dsc.kind == "mamba":
        p = {
            "ln": rmsnorm_spec(d),
            "mamba": ssm_mod.mamba_spec(d, cfg.ssm, dt),
        }
        if dsc.shared_attn:
            # this hybrid layer's own: the LoRA on the tied block's
            # gate/up projection, and the linear into the Mamba input
            r = cfg.ssm.adapter_rank
            p["adapter_a"] = Par((d, r), ("embed", None), init="scaled",
                                 dtype=dt)
            p["adapter_b"] = Par((r, 2 * cfg.d_ff), (None, "ffn"),
                                 init="scaled", dtype=dt)
            p["linear"] = Par((d, d), ("embed", None), init="scaled",
                              dtype=dt)
        return p
    if dsc.kind == "rwkv":
        return {
            "ln_tm": rmsnorm_spec(d),
            "tm": rwkv_mod.timemix_spec(d, cfg.rwkv, dt),
            "ln_cm": rmsnorm_spec(d),
            "cm": rwkv_mod.channelmix_spec(d, cfg.d_ff, dt),
        }
    raise ValueError(dsc.kind)


def shared_block_spec(cfg: ModelConfig) -> dict:
    """zamba2's weight-tied transformer block operating on
    concat(x, x0): attention from 2 * d_model to d_model, then the
    gated MLP (``lm._hybrid_in``, ``lm._hybrid_out``)."""
    d, dt = cfg.d_model, cfg.dtype
    return {
        "ln_in": rmsnorm_spec(2 * d),
        "attn": attn_mod.attn_spec(2 * d, cfg.attention, dt, d_out=d),
        "ln_ffn": rmsnorm_spec(d),
        "ffn": ffn_mod.dense_ffn_spec(d, cfg.d_ff, cfg.activation, dt),
    }


def stage_spec(cfg: ModelConfig, stage: StageDescr) -> dict:
    unit = {f"pos{i}": layer_spec(cfg, dsc)
            for i, dsc in enumerate(stage.unit)}
    return stack(unit, stage.n_units)


# ---------------------------------------------------------------------------
# cache specs (decode state)


def kv_cache_spec(cfg: ModelConfig, batch: int, L: int) -> Par:
    """One layer's attention K or V cache, [B, KV, hd, L]: sequence
    last, so that the row-major layout every program takes by default
    is the sequence-minor one in which the decode step writes a row in
    place and attention reads it (``attention.decode_attention``)."""
    a = cfg.attention
    return Par((batch, a.num_kv_heads, a.head_dim, L),
               ("batch", "kv_heads", None, "kv_seq"), init="zeros",
               dtype=cfg.dtype)


def layer_cache_spec(cfg: ModelConfig, dsc: LayerDescr, batch: int,
                     cache_len: int, windowed: bool = False) -> dict:
    dt = cfg.dtype
    a = cfg.attention
    if dsc.kind in ("attn", "enc_attn"):
        L = cache_len
        if windowed and dsc.window > 0:
            # ring buffer: a sliding-window layer never attends past
            # `window` tokens back, so its cache is O(window), not
            # O(seq) — the big long-context memory lever for
            # local:global archs like gemma3 (see §Perf).
            L = min(cache_len, dsc.window)
        return {
            "k": kv_cache_spec(cfg, batch, L),
            "v": kv_cache_spec(cfg, batch, L),
        }
    if dsc.kind == "dec_attn":
        ek = cfg.encdec.cross_kv_len
        return {
            "k": kv_cache_spec(cfg, batch, cache_len),
            "v": kv_cache_spec(cfg, batch, cache_len),
            "ck": Par((batch, ek, a.num_kv_heads, a.head_dim),
                      ("batch", None, "kv_heads", None), init="zeros",
                      dtype=dt),
            "cv": Par((batch, ek, a.num_kv_heads, a.head_dim),
                      ("batch", None, "kv_heads", None), init="zeros",
                      dtype=dt),
        }
    if dsc.kind == "mamba":
        c = ssm_mod.mamba_state_spec(batch, cfg.d_model, cfg.ssm, dt)
        if dsc.shared_attn:
            c["shared_k"] = kv_cache_spec(cfg, batch, cache_len)
            c["shared_v"] = kv_cache_spec(cfg, batch, cache_len)
        return c
    if dsc.kind == "rwkv":
        return rwkv_mod.rwkv_state_spec(batch, cfg.d_model, cfg.rwkv, dt)
    raise ValueError(dsc.kind)


def stage_cache_spec(cfg: ModelConfig, stage: StageDescr, batch: int,
                     cache_len: int, windowed: bool = False) -> dict:
    unit = {f"pos{i}": layer_cache_spec(cfg, dsc, batch, cache_len,
                                        windowed)
            for i, dsc in enumerate(stage.unit)}
    return stack(unit, stage.n_units)


# ---------------------------------------------------------------------------
# tree helpers


def tree_index(tree, i):
    """Static or traced index into the leading (stack) axis."""
    if isinstance(i, int):
        return jax.tree.map(lambda a: a[i], tree)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)
