"""The system under test, built from a configuration file: the
program's ``ModelConfig`` and its parameter tree filled with the
benchmark's seeded weights."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench import weights as W

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
            cfg.attention, head_dim=W.dims(c)["head_dim"],
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


def _pad_vocab(t, rows: int):
    return jnp.pad(t, ((0, rows - t.shape[0]), (0, 0)))


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
    p = {"embed": _pad_vocab(g["embed"], cfg.padded_vocab),
         "final_norm": g["final_norm"],
         "stage0": {"pos0": {
             "ln_attn": L["attn_norm"], "attn": attn,
             "ln_ffn": L["ffn_norm"],
             "ffn": {"w_gate": L["w_gate"], "w_up": L["w_up"],
                     "w_down": L["w_down"]}}}}
    if not m["tied"]:
        p["lm_head"] = _pad_vocab(g["lm_head"], cfg.padded_vocab)
    return p


def check_tree(cfg, shapes) -> None:
    """Raise unless ``shapes`` (of ``to_program``'s output) has exactly
    the leaves, shapes and dtypes of ``lm.model_spec(cfg)``."""
    from repro.models import lm
    from repro.models.spec import is_par
    want = jax.tree.map(lambda p: (tuple(p.shape), jnp.dtype(p.dtype)),
                        lm.model_spec(cfg), is_leaf=is_par)
    got = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)),
                       shapes)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError(f"weights do not match the program's spec for "
                         f"{cfg.name}:\n want {want}\n got  {got}")


def make_params(cfg, m: dict, seed: int):
    """The program's parameters from ``seed``, made on the device in one
    jitted call, in the dtypes they are served in."""
    fn = jax.jit(lambda key: to_program(cfg, m, W.make_all(m, key)))
    key = W.base_key(seed)
    check_tree(cfg, jax.eval_shape(fn, key))
    return fn(key)
