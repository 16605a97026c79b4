"""Attention layers: GQA self-attention (full / sliding-window / causal),
decode-with-cache, and cross-attention (enc-dec).

Implementation notes
--------------------
* One code path serves gemma3's 5:1 local:global pattern: the window size
  and rope theta enter as *traced per-layer metadata* (values, not
  shapes), so the layer stack scans over a single program — the MultiVic
  requirement of input-independent dataflow holds by construction.
* Training/prefill attention is computed in chunks with an online
  softmax (flash-attention dataflow) so the dry-run's memory analysis
  reflects a deployable program.  ``chunk_q/chunk_kv <= 0`` selects the
  single-block path (used by tests and by the roofline cost pieces,
  where it is FLOP-identical).
* All softmax arithmetic is fp32 regardless of model dtype.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import AttentionConfig
from repro.models.common import apply_rope, rmsnorm, rmsnorm_spec
from repro.models.spec import Par
from repro.obs.blocks import ATTN_CORE, ATTN_PROJ

NEG_INF = -1e30
_BIG_WINDOW = 1 << 30


# ---------------------------------------------------------------------------
# parameter specs


def attn_spec(d_model: int, a: AttentionConfig, dtype: str,
              d_out: Optional[int] = None) -> dict:
    hd, H, KV = a.head_dim, a.num_heads, a.num_kv_heads
    # "head_dim" resolves to the model axis only under the `kvshard`
    # rules variant AND only when the heads dim couldn't take it
    # (divisibility fallback) — see sharding/rules.py.
    p = {
        "wq": Par((d_model, H, hd), ("embed", "heads", "head_dim"),
                  init="scaled", dtype=dtype, fan_in=d_model),
        "wk": Par((d_model, KV, hd), ("embed", "kv_heads", "head_dim"),
                  init="scaled", dtype=dtype, fan_in=d_model),
        "wv": Par((d_model, KV, hd), ("embed", "kv_heads", "head_dim"),
                  init="scaled", dtype=dtype, fan_in=d_model),
        "wo": Par((H, hd, d_out or d_model), ("heads", "head_dim",
                                              "embed"),
                  init="scaled", dtype=dtype, fan_in=H * hd),
    }
    if a.qkv_bias:
        p["bq"] = Par((H, hd), ("heads", None), init="zeros", dtype=dtype)
        p["bk"] = Par((KV, hd), ("kv_heads", None), init="zeros", dtype=dtype)
        p["bv"] = Par((KV, hd), ("kv_heads", None), init="zeros", dtype=dtype)
    if a.qk_norm:
        p["q_norm"] = rmsnorm_spec(hd)
        p["k_norm"] = rmsnorm_spec(hd)
    return p


# ---------------------------------------------------------------------------
# projections


@jax.named_scope(ATTN_PROJ)
def qkv_project(p: dict, x: jax.Array, a: AttentionConfig,
                positions: jax.Array, theta) -> Tuple[jax.Array, jax.Array,
                                                      jax.Array]:
    """x: [B, S, d] -> q [B,S,H,hd], k/v [B,S,KV,hd] (rope applied)."""
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    if a.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if a.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if a.rope_theta > 0:  # static per-arch; whisper uses no rope
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


@jax.named_scope(ATTN_PROJ)
def out_project(p: dict, o: jax.Array) -> jax.Array:
    return jnp.einsum("bsnh,nhd->bsd", o, p["wo"])


# ---------------------------------------------------------------------------
# masked scaled-dot-product attention, chunked with online softmax


def _mask_bias(pos_q: jax.Array, pos_k: jax.Array, causal: bool,
               window) -> jax.Array:
    """[Sq, Tk] additive bias in fp32.  ``window`` may be traced."""
    dq = pos_q[:, None].astype(jnp.int32)
    dk = pos_k[None, :].astype(jnp.int32)
    ok = dk >= 0          # ring-buffer slots not yet written are < 0
    if causal:
        ok = ok & (dk <= dq)
    w_eff = jnp.where(jnp.asarray(window, jnp.int32) > 0,
                      jnp.asarray(window, jnp.int32), _BIG_WINDOW)
    ok = ok & (dq - dk < w_eff)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _block_attn(q: jax.Array, k: jax.Array, v: jax.Array, bias: jax.Array,
                scale: float) -> jax.Array:
    """Single-block reference attention.
    q: [B,Sq,KV,G,hd]; k,v: [B,Tk,KV,hd]; bias: [Sq,Tk]."""
    s = jnp.einsum("bqkgh,btkh->bkgqt", q, k).astype(jnp.float32) * scale
    s = s + bias[None, None, None]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkh->bqkgh", p.astype(v.dtype), v)
    return o


@jax.named_scope(ATTN_CORE)
def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, pos_q: jax.Array,
         pos_k: jax.Array, *, causal: bool, window, scale: float,
         chunk_q: int = 0, chunk_kv: int = 0) -> jax.Array:
    """Grouped-query attention.  q: [B,Sq,H,hd] with H = KV*G;
    k,v: [B,Tk,KV,hd].  Returns [B,Sq,H,hd]."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)

    if chunk_q > 0 and Sq % chunk_q != 0:
        chunk_q = 0                       # graceful single-block fallback
    if chunk_kv > 0 and k.shape[1] % chunk_kv != 0:
        chunk_kv = 0

    if chunk_q <= 0 or chunk_q >= Sq:
        bias = _mask_bias(pos_q, pos_k, causal, window)
        o = _block_attn(qg, k, v, bias, scale)
        return o.reshape(B, Sq, H, hd)

    assert Sq % chunk_q == 0, (Sq, chunk_q)
    nq = Sq // chunk_q
    qc = jnp.moveaxis(qg.reshape(B, nq, chunk_q, KV, G, hd), 1, 0)
    pqc = pos_q.reshape(nq, chunk_q)

    Tk = k.shape[1]
    use_kv_chunks = chunk_kv > 0 and chunk_kv < Tk
    if use_kv_chunks:
        assert Tk % chunk_kv == 0, (Tk, chunk_kv)
        nk = Tk // chunk_kv
        kc = jnp.moveaxis(k.reshape(B, nk, chunk_kv, KV, hd), 1, 0)
        vc = jnp.moveaxis(v.reshape(B, nk, chunk_kv, KV, hd), 1, 0)
        pkc = pos_k.reshape(nk, chunk_kv)

    @jax.checkpoint
    def q_step(_, qi):
        # rematerialized in the backward pass (flash-attention-style):
        # per-q-chunk softmax stats are recomputed, never stored for the
        # whole sequence.
        qq, pq = qi
        if not use_kv_chunks:
            bias = _mask_bias(pq, pos_k, causal, window)
            return None, _block_attn(qq, k, v, bias, scale)

        # online softmax over kv chunks
        m0 = jnp.full((B, KV, G, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, chunk_q), jnp.float32)
        a0 = jnp.zeros((B, KV, G, chunk_q, hd), jnp.float32)

        def kv_step(carry, ki):
            m, l, acc = carry
            kk, vv, pk = ki
            s = jnp.einsum("bqkgh,btkh->bkgqt", qq, kk).astype(jnp.float32)
            s = s * scale + _mask_bias(pq, pk, causal, window)[None, None,
                                                              None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            pexp = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + pexp.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", pexp, vv.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kc, vc, pkc))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, jnp.einsum("bkgqh->bqkgh", o).astype(q.dtype)

    _, oc = jax.lax.scan(q_step, None, (qc, pqc))
    # oc: [nq, B, chunk_q, KV, G, hd] -> [B, Sq, H, hd]
    o = jnp.moveaxis(oc, 0, 1).reshape(B, Sq, KV, G, hd)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# layer-level entry points


def self_attention(p: dict, x: jax.Array, a: AttentionConfig,
                   positions: jax.Array, *, theta, window,
                   chunk_q: int = 512, chunk_kv: int = 512,
                   return_kv: bool = False, causal: bool = True):
    """Training / prefill self-attention over the whole sequence."""
    scale = a.softmax_scale or 1.0 / math.sqrt(a.head_dim)
    q, k, v = qkv_project(p, x, a, positions, theta)
    o = sdpa(q, k, v, positions, positions, causal=causal, window=window,
             scale=scale, chunk_q=chunk_q, chunk_kv=chunk_kv)
    y = out_project(p, o)
    if return_kv:
        return y, (k, v)
    return y


def decode_attention(p: dict, x: jax.Array, a: AttentionConfig,
                     cache_k: jax.Array, cache_v: jax.Array, layer,
                     pos, *, theta, window):
    """Single-token decode.  x: [B, 1, d]; cache_k/v: [N, B, KV, hd, L],
    the stacked caches of N layers (``blocks.kv_cache_spec``), of which
    this one is ``layer``; ``pos`` is the (traced) index of the new token.

    Only the new token's row is written, in place in the stack, and
    attention reads this layer's slice by index, so no layer's cache is
    copied out or written back whole.  If the cache is SHORTER than the
    attention span could be (windowed ring buffer, L == window for a
    local layer), the write lands at pos % L and per-slot positions are
    reconstructed — slot s holds the newest position p <= pos with
    p % L == s.  Returns (y [B,1,d], new_cache_k, new_cache_v)."""
    scale = a.softmax_scale or 1.0 / math.sqrt(a.head_dim)
    positions = jnp.asarray(pos, jnp.int32)[None]
    q, k_new, v_new = qkv_project(p, x, a, positions, theta)
    zero = jnp.zeros((), jnp.int32)
    pos_i = jnp.asarray(pos, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    L = cache_k.shape[-1]
    is_ring = window > 0 and L <= window if isinstance(window, int) \
        else False
    slot = pos_i % L if is_ring else pos_i
    with jax.named_scope(ATTN_CORE):
        at = (layer, zero, zero, zero, slot)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, jnp.moveaxis(k_new, 1, -1)[None].astype(cache_k.dtype),
            at)
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, jnp.moveaxis(v_new, 1, -1)[None].astype(cache_v.dtype),
            at)
        # attention reads the layer in the layout it is stored in, so
        # the read fuses into its products; where the head dim fills the
        # lanes the compiler would otherwise copy the layer out into a
        # head-dim-minor layout first
        k = compat.with_row_major_layout(
            jax.lax.dynamic_index_in_dim(cache_k, layer, 0, keepdims=False))
        v = compat.with_row_major_layout(
            jax.lax.dynamic_index_in_dim(cache_v, layer, 0, keepdims=False))
    s_idx = jnp.arange(L, dtype=jnp.int32)
    if is_ring:
        # newest position in each slot; slots "ahead" of pos wrap to
        # negative and are masked by the causal check in sdpa
        pos_k = pos_i - ((pos_i - s_idx) % L)
    else:
        pos_k = s_idx
    o = sdpa(q, jnp.moveaxis(k, -1, 1), jnp.moveaxis(v, -1, 1), positions,
             pos_k, causal=True, window=window, scale=scale, chunk_q=0,
             chunk_kv=0)
    return out_project(p, o), cache_k, cache_v


def cross_attention(p: dict, x: jax.Array, mem_k: jax.Array,
                    mem_v: jax.Array, a: AttentionConfig) -> jax.Array:
    """Enc-dec cross attention; memory K/V are precomputed from encoder
    output.  No mask (encoder memory fully visible)."""
    scale = a.softmax_scale or 1.0 / math.sqrt(a.head_dim)
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    if a.qkv_bias:
        q = q + p["bq"]
    pos_q = jnp.arange(x.shape[1], dtype=jnp.int32)
    pos_k = jnp.arange(mem_k.shape[1], dtype=jnp.int32)
    o = sdpa(q, mem_k, mem_v, pos_q, pos_k, causal=False, window=0,
             scale=scale, chunk_q=0, chunk_kv=0)
    return out_project(p, o)


def cross_kv(p: dict, memory: jax.Array, a: AttentionConfig):
    """Project encoder output once into cross-attention K/V."""
    k = jnp.einsum("bsd,dnh->bsnh", memory, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", memory, p["wv"])
    if a.qkv_bias:
        k = k + p["bk"]
        v = v + p["bv"]
    return k, v
