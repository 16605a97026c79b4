"""Mamba2 (SSD — state-space duality) blocks, used by zamba2-7b.

The recurrence  h_t = exp(a_t) h_{t-1} + dt_t * B_t x_t^T,
                y_t = C_t · h_t + D * x_t
is computed in the chunked (matrix) form: intra-chunk attention-like
term + inter-chunk state carry via lax.scan.  Deterministic dataflow —
static-schedulable per the paper's requirement.  Exponents of the decay
segments are always <= 0 (scalar per-head decay), so the chunked form is
numerically stable without rescaling.

B and C come in ``n_groups`` groups: head h reads group
h // (heads / n_groups).  The output is gated by silu(z) and then
RMS-normalised per group of d_inner / n_groups channels.

Scopes: ``ssm_proj`` holds the input and output projections, ``ssm_core``
the convolution, the scan or state update, the D skip and the gated
norm.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.spec import Par
from repro.obs.blocks import SSM_CORE, SSM_PROJ

# bound on the intra-chunk temporaries of one ``ssd_chunked`` call: the
# batch is taken in blocks of rows small enough to keep under it
SCAN_TEMP_BYTES = 1 << 28


def ssm_dims(d_model: int, s: SSMConfig):
    d_inner = s.expand * d_model
    nheads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.state_dim
    return d_inner, nheads, conv_dim


def mamba_spec(d_model: int, s: SSMConfig, dtype: str) -> dict:
    d_inner, nheads, conv_dim = ssm_dims(d_model, s)
    assert nheads % s.n_groups == 0, (nheads, s.n_groups)
    d_in_proj = d_inner + conv_dim + nheads
    return {
        "in_proj": Par((d_model, d_in_proj), ("embed", "ffn"), init="scaled",
                       dtype=dtype),
        "conv_w": Par((s.conv_kernel, conv_dim), (None, "ffn"),
                      init="scaled", dtype=dtype),
        "conv_b": Par((conv_dim,), ("ffn",), init="zeros", dtype=dtype),
        "A_log": Par((nheads,), (None,), init="decay", dtype="float32"),
        "D": Par((nheads,), (None,), init="ones", dtype="float32"),
        "dt_bias": Par((nheads,), (None,), init="zeros", dtype="float32"),
        "norm": Par((d_inner,), (None,), init="ones", dtype="float32"),
        "out_proj": Par((d_inner, d_model), ("ffn", "embed"), init="scaled",
                        dtype=dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv1d.  x: [B,S,C]; w: [K,C]; state: [B,K-1,C]
    carries the last K-1 inputs for decode.  Returns (y, new_state)."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)          # [B, S+K-1, C]
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):] if K > 1 else pad
    return y, new_state


def _split_proj(zxbcdt: jax.Array, d_inner: int, conv_dim: int,
                nheads: int):
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = zxbcdt[..., -nheads:]
    return z, xBC, dt


def _split_xbc(xBC: jax.Array, d_inner: int, s: SSMConfig):
    """x [..., d_inner], B and C [..., G, N] of the convolved input."""
    GN = s.n_groups * s.state_dim
    grouped = xBC.shape[:-1] + (s.n_groups, s.state_dim)
    return (xBC[..., :d_inner],
            xBC[..., d_inner:d_inner + GN].reshape(grouped),
            xBC[..., d_inner + GN:].reshape(grouped))


def gated_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int,
               eps: float) -> jax.Array:
    """RMSNorm of y * silu(z) over each of ``groups`` equal groups of
    channels, then the gain ``w``."""
    h = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = h.reshape(h.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return (g.reshape(h.shape) * w.astype(jnp.float32)).astype(y.dtype)


def _ssd(x, a, Bm, Cm, chunk, init_state):
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[-2:]
    Hg = H // G
    assert S % chunk == 0, (S, chunk)
    NC = S // chunk
    xc = x.reshape(Bsz, NC, chunk, G, Hg, P)
    ac = a.reshape(Bsz, NC, chunk, G, Hg).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, NC, chunk, G, N)
    Cc = Cm.reshape(Bsz, NC, chunk, G, N)

    ca = jnp.cumsum(ac, axis=2)                      # inclusive [B,NC,L,G,Hg]
    total = ca[:, :, -1]                             # [B,NC,G,Hg]

    # intra-chunk: y[t] += sum_{j<=t} (C_t.B_j) exp(ca_t - ca_j) x_j
    seg = ca[:, :, :, None] - ca[:, :, None, :]      # [B,NC,L(t),L(j),G,Hg]
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    seg = jnp.where(tri[None, None, :, :, None, None], jnp.exp(seg), 0.0)
    cb = jnp.einsum("bctgn,bcjgn->bctjg", Cc.astype(jnp.float32),
                    Bc.astype(jnp.float32))
    att = (cb[..., None] * seg).astype(x.dtype)       # [B,NC,L,L,G,Hg]
    y_intra = jnp.einsum("bctjgh,bcjghp->bctghp", att, xc)

    # chunk boundary states: sum_j exp(total - ca_j) B_j x_j^T
    decay_end = jnp.exp(total[:, :, None] - ca)       # [B,NC,L,G,Hg]
    cstate = jnp.einsum("bclgh,bclgn,bclghp->bcghnp",
                        decay_end.astype(x.dtype), Bc.astype(x.dtype), xc)

    s0 = (jnp.zeros((Bsz, H, N, P), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def boundary(carry, inp):
        cs, tot = inp                                 # [B,H,N,P], [B,H]
        new = carry * jnp.exp(tot)[:, :, None, None] + cs.astype(jnp.float32)
        return new, carry                             # emit state BEFORE

    total_t = jnp.moveaxis(total.reshape(Bsz, NC, H), 1, 0)   # [NC,B,H]
    cstate_t = jnp.moveaxis(cstate.reshape(Bsz, NC, H, N, P), 1, 0)
    final, prev_states = jax.lax.scan(boundary, s0, (cstate_t, total_t))
    prev_states = jnp.moveaxis(prev_states, 0, 1)     # [B,NC,H,N,P]

    # inter-chunk: y[t] += exp(ca_t) * C_t . S_prev
    y_inter = jnp.einsum(
        "bctgn,bcghnp->bctghp", Cc.astype(x.dtype),
        prev_states.reshape(Bsz, NC, G, Hg, N, P).astype(x.dtype))
    y_inter = y_inter * jnp.exp(ca)[..., None].astype(x.dtype)

    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y, final.astype(x.dtype)


def scan_rows(batch: int, seq: int, nheads: int, chunk: int) -> int:
    """Rows of the batch one block of ``ssd_chunked`` takes so that its
    intra-chunk temporaries (the float32 decay segments and their
    product with C.B in the model's dtype, [rows, S/L, L, L, H]) stay
    under ``SCAN_TEMP_BYTES``: the largest divisor of ``batch`` that
    does, and at least 1."""
    per_row = seq * chunk * nheads * (4 + 2)
    rows = max(1, min(batch, SCAN_TEMP_BYTES // per_row))
    while batch % rows:
        rows -= 1
    return rows


def ssd_chunked(x: jax.Array, a: jax.Array, Bm: jax.Array, Cm: jax.Array,
                chunk: int, init_state: Optional[jax.Array] = None,
                rows: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x:  [B,S,H,P]  (already multiplied by dt)
    a:  [B,S,H]    log-decay per step (<= 0)
    Bm: [B,S,G,N], Cm: [B,S,G,N]; head h reads group h // (H / G)
    rows: 0 takes the whole batch at once; else blocks of ``rows`` rows,
    one after another (the same arithmetic, bounded temporaries)
    Returns (y [B,S,H,P], final_state [B,H,N,P]).
    """
    Bsz = x.shape[0]
    if not rows or rows >= Bsz:
        return _ssd(x, a, Bm, Cm, chunk, init_state)
    assert Bsz % rows == 0, (Bsz, rows)
    if init_state is None:
        H, P, N = x.shape[2], x.shape[3], Bm.shape[-1]
        init_state = jnp.zeros((Bsz, H, N, P), jnp.float32)

    def blocks(t):
        return t.reshape((Bsz // rows, rows) + t.shape[1:])

    y, final = jax.lax.map(lambda t: _ssd(*t[:4], chunk, t[4]),
                           tuple(map(blocks, (x, a, Bm, Cm, init_state))))
    return (y.reshape((Bsz,) + y.shape[2:]),
            final.reshape((Bsz,) + final.shape[2:]))


def mamba_forward(p: dict, x: jax.Array, s: SSMConfig,
                  state: Optional[dict] = None, return_state: bool = False,
                  *, eps: float):
    """Full-sequence Mamba2 block.  x: [B,S,d]."""
    d_model = x.shape[-1]
    d_inner, nheads, conv_dim = ssm_dims(d_model, s)
    with jax.named_scope(SSM_PROJ):
        zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    with jax.named_scope(SSM_CORE):
        z, xBC, dt = _split_proj(zxbcdt, d_inner, conv_dim, nheads)
        conv_state = None if state is None else state["conv"]
        xBC, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"],
                                     conv_state)
        xin, Bm, Cm = _split_xbc(jax.nn.silu(xBC), d_inner, s)

        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        a = -jnp.exp(p["A_log"]) * dt                             # <= 0
        xh = xin.reshape(*xin.shape[:-1], nheads, s.head_dim)
        xdt = xh * dt[..., None].astype(xh.dtype)

        init_ssm = None if state is None else state["ssm"]
        B, S = x.shape[:2]
        chunk = s.chunk_size if S % s.chunk_size == 0 else S
        y, final = ssd_chunked(xdt, a, Bm, Cm, chunk, init_ssm,
                               rows=scan_rows(B, S, nheads, chunk))
        y = y + xh * p["D"][:, None].astype(xh.dtype)
        y = gated_norm(y.reshape(*x.shape[:-1], d_inner), z, p["norm"],
                       s.n_groups, eps)
    with jax.named_scope(SSM_PROJ):
        out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    if return_state:
        return out, {"conv": new_conv, "ssm": final}
    return out


def mamba_decode(p: dict, x: jax.Array, s: SSMConfig, state: dict, *,
                 eps: float):
    """Single-token decode.  x: [B,1,d]; state {conv [B,K-1,C],
    ssm [B,H,N,P]}."""
    d_model = x.shape[-1]
    d_inner, nheads, conv_dim = ssm_dims(d_model, s)
    G = s.n_groups
    with jax.named_scope(SSM_PROJ):
        zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    with jax.named_scope(SSM_CORE):
        z, xBC, dt = _split_proj(zxbcdt, d_inner, conv_dim, nheads)
        xBC, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"],
                                     state["conv"])
        xin, Bm, Cm = _split_xbc(jax.nn.silu(xBC[:, 0]), d_inner, s)

        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        a = jnp.exp(-jnp.exp(p["A_log"]) * dt)                    # [B,1,H]
        Bsz = x.shape[0]
        xh = xin.reshape(Bsz, nheads, s.head_dim)                 # [B,H,P]
        xdt = xh * dt[:, 0, :, None].astype(xh.dtype)

        S0 = state["ssm"].astype(jnp.float32)                     # [B,H,N,P]
        grouped = (Bsz, G, nheads // G) + S0.shape[2:]
        upd = jnp.einsum("bgn,bghp->bghnp", Bm.astype(jnp.float32),
                         xdt.reshape(Bsz, G, nheads // G, s.head_dim)
                         .astype(jnp.float32))
        S1 = S0.reshape(grouped) * a[:, 0].reshape(Bsz, G, -1, 1, 1) + upd
        y = jnp.einsum("bgn,bghnp->bghp", Cm.astype(jnp.float32), S1)
        y = y.reshape(Bsz, nheads, s.head_dim).astype(xh.dtype) \
            + xh * p["D"][:, None].astype(xh.dtype)
        y = gated_norm(y.reshape(Bsz, 1, d_inner), z, p["norm"], G, eps)
    with jax.named_scope(SSM_PROJ):
        out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, {"conv": new_conv,
                 "ssm": S1.reshape(S0.shape).astype(state["ssm"].dtype)}


def mamba_state_spec(batch: int, d_model: int, s: SSMConfig,
                     dtype: str) -> dict:
    d_inner, nheads, conv_dim = ssm_dims(d_model, s)
    return {
        "conv": Par((batch, s.conv_kernel - 1, conv_dim),
                    ("batch", None, "ffn"), init="zeros", dtype=dtype),
        "ssm": Par((batch, nheads, s.state_dim, s.head_dim),
                   ("batch", "heads", None, None), init="zeros",
                   dtype=dtype),
    }
