"""Compositional cost pieces — the paper's timing-compositionality
(§3.1) applied to roofline accounting.

``cost_analysis()`` counts a ``lax.scan`` body once (verified
empirically), so a whole-model lowering under-reports FLOPs/bytes/
collectives by the layer count.  Instead of unrolling 95-layer models
at 512 devices, we lower each *repeat unit* separately (with the true
shardings) and compose:

    total = sum over pieces ( piece_cost x multiplier )

Pieces are chosen so that each piece's internal scans are degenerate:
 * attention units lower with chunk_q=chunk_kv=0 (single-block attention
   is FLOP-identical to the chunked schedule),
 * recurrent units (Mamba2/RWKV6) lower at one chunk of sequence with
   multiplier n_units * (S / chunk)  (all their costs are linear in S),
 * zamba2's quadratic shared-attention block is split out as its own
   full-sequence piece,
 * the loss lowers with loss_chunk=0,
 * the optimizer update is one piece over the full parameter tree.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.models import attention as attn_mod
from repro.models import blocks as blk
from repro.models import lm as lm_mod
from repro.models.lm import RunOptions
from repro.models.spec import shape_tree
from repro.optim.adamw import adamw_init_spec, adamw_update, cosine_lr
from repro.sharding.rules import ShardingRules


@dataclass
class Piece:
    name: str
    multiplier: float
    fn: Callable
    specs: Tuple


def _sds(shape, dtype, rules, axes):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                sharding=rules.sharding_for(axes, shape))


def _x_spec(cfg, B, S, rules):
    return _sds((B, S, cfg.d_model), cfg.dtype, rules,
                ("batch", None, None))


def _unit_param_specs(cfg, stage: blk.StageDescr, rules):
    unit = {f"pos{i}": blk.layer_spec(cfg, dsc)
            for i, dsc in enumerate(stage.unit)}
    return shape_tree(unit, rules)


def _unit_cache_specs(cfg, stage, B, cache_len, rules):
    unit = {f"pos{i}": blk.layer_cache_spec(cfg, dsc, B, cache_len)
            for i, dsc in enumerate(stage.unit)}
    return shape_tree(unit, rules)


def _shared_specs(cfg, rules):
    from repro.models.spec import stack
    return shape_tree(stack(blk.shared_block_spec(cfg),
                            cfg.ssm.n_shared_blocks), rules)


def _dec_len(cfg, shape) -> int:
    if cfg.family == "encdec":
        return max(256, shape.seq_len // cfg.encdec.dec_len_ratio)
    return shape.seq_len


def _is_recurrent_stage(stage: blk.StageDescr) -> bool:
    return any(d.kind in ("mamba", "rwkv") for d in stage.unit)


def _positions(S):
    return jnp.arange(S, dtype=jnp.int32)


def _unit_fn(cfg, stage, opts, *, train: bool, collect: bool,
             has_shared: bool, has_memory: bool):
    """fn(unit_params, x, x0, shared?, memory?) lowering one unit."""

    def fwd(up, x, x0, shared, memory):
        out, aux, cache = lm_mod._apply_unit_full(
            cfg, up, stage, x, x0, _positions(x.shape[1]), opts,
            collect, memory, shared, jnp.zeros((), jnp.int32), x.shape[1])
        loss = out.astype(jnp.float32).sum() + aux
        return (loss, cache) if collect else loss

    if train:
        argnums = (0, 1) + ((3,) if has_shared else ())

        def step(up, x, x0, shared=None, memory=None):
            return jax.grad(fwd, argnums=argnums)(up, x, x0, shared,
                                                  memory)
    else:
        def step(up, x, x0, shared=None, memory=None):
            return fwd(up, x, x0, shared, memory)
    return step


def _strip_shared_attn(stage: blk.StageDescr) -> blk.StageDescr:
    import dataclasses
    unit = tuple(dataclasses.replace(d, shared_attn=False)
                 for d in stage.unit)
    return blk.StageDescr(stage.n_units, unit)


def _chunked_stage_pieces(cfg, stage, si, B, S, rules, opts, train,
                          collect) -> List[Piece]:
    """Recurrent stage: lower one unit at one chunk of sequence."""
    chunk = (cfg.ssm.chunk_size if cfg.family == "hybrid"
             else cfg.rwkv.chunk_size)
    chunk = min(chunk, S)
    n_chunks = S // chunk
    pieces = []
    # shared-attention applications (zamba2) — full-sequence quadratic
    n_shared_apps = sum(1 for d in stage.unit if d.shared_attn) \
        * stage.n_units
    if n_shared_apps:
        a = cfg.attention

        def shared_fn(shared, hp, x, x0):
            def fwd(shared, hp, x, x0):
                sp = blk.tree_index(shared, 0)
                att = attn_mod.self_attention(
                    sp["attn"], lm_mod._hybrid_in(cfg, sp, x, x0), a,
                    _positions(x.shape[1]), theta=a.rope_theta, window=0,
                    chunk_q=0, chunk_kv=0)
                out = lm_mod._hybrid_out(cfg, sp, hp, att)
                return out.astype(jnp.float32).sum()
            if train:
                return jax.grad(fwd, argnums=(0, 1, 2))(shared, hp, x, x0)
            return fwd(shared, hp, x, x0)
        hybrid = next(d for d in stage.unit if d.shared_attn)
        own = {k: v for k, v in blk.layer_spec(cfg, hybrid).items()
               if k in ("adapter_a", "adapter_b", "linear")}
        pieces.append(Piece(
            f"stage{si}_shared_attn", n_shared_apps, shared_fn,
            (_shared_specs(cfg, rules), shape_tree(own, rules),
             _x_spec(cfg, B, S, rules), _x_spec(cfg, B, S, rules))))

    stage1 = blk.StageDescr(1, _strip_shared_attn(
        blk.StageDescr(1, (stage.unit[-1],))).unit)
    n_layers = stage.n_units * stage.unit_len
    fn = _unit_fn(cfg, stage1, opts, train=train, collect=collect,
                  has_shared=False, has_memory=False)
    pieces.append(Piece(
        f"stage{si}_unit_chunk", n_layers * n_chunks, fn,
        (_unit_param_specs(cfg, stage1, rules),
         _x_spec(cfg, B, chunk, rules), _x_spec(cfg, B, chunk, rules))))
    return pieces


def train_pieces(cfg: ModelConfig, shape: ShapeConfig,
                 rules: ShardingRules, opts: RunOptions) -> List[Piece]:
    B, S = shape.global_batch, _dec_len(cfg, shape)
    # exact-FLOP single-block attention + unchunked loss for pieces
    popts = RunOptions(chunk_q=0, chunk_kv=0, loss_chunk=0, remat=False,
                       shardings=opts.shardings, moe_impl=opts.moe_impl)
    pieces: List[Piece] = []

    for si, stage in enumerate(blk.build_stages(cfg)):
        if _is_recurrent_stage(stage):
            pieces += _chunked_stage_pieces(cfg, stage, si, B, S, rules,
                                            popts, True, False)
            continue
        has_mem = any(d.kind == "dec_attn" for d in stage.unit)
        fn = _unit_fn(cfg, stage, popts, train=True, collect=False,
                      has_shared=False, has_memory=has_mem)
        specs = [
            _unit_param_specs(cfg, stage, rules),
            _x_spec(cfg, B, S, rules), _x_spec(cfg, B, S, rules)]
        if has_mem:
            specs.append(None)   # shared placeholder
            specs.append(_x_spec(cfg, B, shape.seq_len, rules))
        pieces.append(Piece(f"stage{si}_unit", stage.n_units, fn,
                            tuple(specs)))

    if cfg.family == "encdec":
        enc = blk.encoder_stage(cfg)
        fn = _unit_fn(cfg, enc, popts, train=True, collect=False,
                      has_shared=False, has_memory=False)
        pieces.append(Piece(
            "encoder_unit", enc.n_units, fn,
            (_unit_param_specs(cfg, enc, rules),
             _x_spec(cfg, B, shape.seq_len, rules),
             _x_spec(cfg, B, shape.seq_len, rules))))

    # embedding + loss (fwd+bwd)
    def embed_loss(ep, tokens, targets, x_fin):
        def fwd(ep, x_fin):
            x = lm_mod._embed(cfg, ep, tokens, None, popts)
            from repro.models.common import rmsnorm
            h = rmsnorm(x_fin, ep["final_norm"])
            loss = lm_mod.lm_loss(cfg, ep, h, targets, popts)
            return loss + jnp.float32(1e-9) * x.astype(jnp.float32).sum()
        return jax.grad(fwd, argnums=(0, 1))(ep, x_fin)

    from repro.models.spec import Par
    ep_spec = {"embed": shape_tree(
        Par((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
            dtype=cfg.dtype), rules),
        "final_norm": shape_tree(
            Par((cfg.d_model,), (None,), dtype="float32"), rules)}
    if not cfg.tie_embeddings:
        ep_spec["lm_head"] = shape_tree(
            Par((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                dtype=cfg.dtype), rules)
    tok = _sds((B, S), jnp.int32, rules, ("batch", None))
    pieces.append(Piece("embed_loss", 1.0, embed_loss,
                        (ep_spec, tok, tok, _x_spec(cfg, B, S, rules))))

    # optimizer update over the whole parameter tree
    tcfg = TrainConfig()
    lr_fn = cosine_lr(tcfg)

    def opt_piece(params, opt_state, grads):
        return adamw_update(grads, opt_state, params, tcfg, lr_fn)

    pspec = shape_tree(lm_mod.model_spec(cfg), rules)
    ospec = shape_tree(adamw_init_spec(lm_mod.model_spec(cfg)), rules)
    pieces.append(Piece("optimizer", 1.0, opt_piece,
                        (pspec, ospec, pspec)))
    return pieces


def prefill_pieces(cfg, shape, rules, opts) -> List[Piece]:
    B, S = shape.global_batch, _dec_len(cfg, shape)
    popts = RunOptions(chunk_q=0, chunk_kv=0, loss_chunk=0, remat=False,
                       shardings=opts.shardings, moe_impl=opts.moe_impl)
    pieces: List[Piece] = []
    for si, stage in enumerate(blk.build_stages(cfg)):
        if _is_recurrent_stage(stage):
            pieces += _chunked_stage_pieces(cfg, stage, si, B, S, rules,
                                            popts, False, False)
            continue
        has_mem = any(d.kind == "dec_attn" for d in stage.unit)
        fn = _unit_fn(cfg, stage, popts, train=False, collect=True,
                      has_shared=False, has_memory=has_mem)
        specs = [_unit_param_specs(cfg, stage, rules),
                 _x_spec(cfg, B, S, rules), _x_spec(cfg, B, S, rules)]
        if has_mem:
            specs.append(None)
            specs.append(_x_spec(cfg, B, shape.seq_len, rules))
        pieces.append(Piece(f"stage{si}_unit", stage.n_units, fn,
                            tuple(specs)))
    if cfg.family == "encdec":
        enc = blk.encoder_stage(cfg)
        fn = _unit_fn(cfg, enc, popts, train=False, collect=False,
                      has_shared=False, has_memory=False)
        pieces.append(Piece(
            "encoder_unit", enc.n_units, fn,
            (_unit_param_specs(cfg, enc, rules),
             _x_spec(cfg, B, shape.seq_len, rules),
             _x_spec(cfg, B, shape.seq_len, rules))))

    def head_fn(ep, tokens, x_fin):
        from repro.models.common import rmsnorm
        x = lm_mod._embed(cfg, ep, tokens, None, popts)
        h = rmsnorm(x_fin[:, -1], ep["final_norm"])
        return lm_mod.compute_logits(cfg, ep, h), x.sum()

    from repro.models.spec import Par
    ep_spec = {"embed": shape_tree(
        Par((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
            dtype=cfg.dtype), rules),
        "final_norm": shape_tree(
            Par((cfg.d_model,), (None,), dtype="float32"), rules)}
    if not cfg.tie_embeddings:
        ep_spec["lm_head"] = shape_tree(
            Par((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                dtype=cfg.dtype), rules)
    tok = _sds((B, S), jnp.int32, rules, ("batch", None))
    pieces.append(Piece("embed_head", 1.0, head_fn,
                        (ep_spec, tok, _x_spec(cfg, B, S, rules))))
    return pieces


def decode_pieces(cfg, shape, rules, opts) -> List[Piece]:
    B = shape.global_batch
    cache_len = shape.seq_len
    popts = RunOptions(chunk_q=0, chunk_kv=0, shardings=opts.shardings,
                       moe_impl=opts.moe_impl)
    pieces: List[Piece] = []
    for si, stage in enumerate(blk.build_stages(cfg)):
        has_shared = any(d.shared_attn for d in stage.unit)

        def mk(stage_, has_shared_):
            def fn(up, cache_u, x, x0, shared=None):
                out, nc = lm_mod._apply_unit_decode(
                    cfg, up, stage_, x, x0, jnp.int32(cache_len - 1),
                    popts, cache_u, shared, jnp.zeros((), jnp.int32))
                return out, nc
            return fn

        specs = [
            _unit_param_specs(cfg, stage, rules),
            _unit_cache_specs(cfg, stage, B, cache_len, rules),
            _x_spec(cfg, B, 1, rules), _x_spec(cfg, B, 1, rules)]
        if has_shared:
            specs.append(_shared_specs(cfg, rules))
        pieces.append(Piece(f"stage{si}_unit", stage.n_units,
                            mk(stage, has_shared), tuple(specs)))

    def head_fn(ep, token, x_fin):
        from repro.models.common import rmsnorm
        x = lm_mod._embed(cfg, ep, token[:, None], None, popts)
        h = rmsnorm(x_fin[:, 0], ep["final_norm"])
        return lm_mod.compute_logits(cfg, ep, h), x.sum()

    from repro.models.spec import Par
    ep_spec = {"embed": shape_tree(
        Par((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
            dtype=cfg.dtype), rules),
        "final_norm": shape_tree(
            Par((cfg.d_model,), (None,), dtype="float32"), rules)}
    if not cfg.tie_embeddings:
        ep_spec["lm_head"] = shape_tree(
            Par((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                dtype=cfg.dtype), rules)
    tok = _sds((B,), jnp.int32, rules, ("batch",))
    pieces.append(Piece("embed_head", 1.0, head_fn,
                        (ep_spec, tok, _x_spec(cfg, B, 1, rules))))
    return pieces


def cost_pieces(cfg: ModelConfig, shape: ShapeConfig,
                rules: ShardingRules, opts: RunOptions) -> List[Piece]:
    if shape.kind == "train":
        return train_pieces(cfg, shape, rules, opts)
    if shape.kind == "prefill":
        return prefill_pieces(cfg, shape, rules, opts)
    return decode_pieces(cfg, shape, rules, opts)
