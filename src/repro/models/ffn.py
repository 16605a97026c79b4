"""Feed-forward layers: dense (SwiGLU / GeGLU / GELU / squared-ReLU) and
capacity-factor mixture-of-experts.

MoE uses GShard-style *static-shape* dispatch: tokens are grouped, each
expert accepts at most ``capacity`` tokens per group, overflow tokens are
dropped (their residual passes through).  This is the MoE that satisfies
the paper's static-scheduling requirement: the compile-time schedule must
not depend on input data, so the "additional assumptions ... during
scheduling" (paper §3) become the capacity factor.  Experts are sharded
on the ``model`` mesh axis (expert parallelism); the dispatch/combine
einsums lower to all-to-all-like collectives under GSPMD.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.common import activate, is_gated
from repro.models.spec import Par
from repro.obs.blocks import FFN


# ---------------------------------------------------------------------------
# dense FFN


def dense_ffn_spec(d_model: int, d_ff: int, activation: str,
                   dtype: str) -> dict:
    p = {
        "w_gate": Par((d_model, d_ff), ("embed", "ffn"), init="scaled",
                      dtype=dtype),
        "w_down": Par((d_ff, d_model), ("ffn", "embed"), init="scaled",
                      dtype=dtype),
    }
    if is_gated(activation):
        p["w_up"] = Par((d_model, d_ff), ("embed", "ffn"), init="scaled",
                        dtype=dtype)
    return p


@jax.named_scope(FFN)
def dense_ffn(p: dict, x: jax.Array, activation: str,
              adapter: Optional[Tuple[jax.Array, jax.Array]] = None
              ) -> jax.Array:
    """``adapter`` (A [d, r], B [r, 2 * d_ff]): a low-rank term
    (x A) B added to the gate (first half) and up projections."""
    hg = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    hu = jnp.einsum("bsd,df->bsf", x, p["w_up"]) if "w_up" in p else None
    if adapter is not None:
        lo = jnp.einsum("bsd,dr->bsr", x, adapter[0])
        ad = jnp.einsum("bsr,rf->bsf", lo, adapter[1])
        hg = hg + ad[..., :hg.shape[-1]]
        hu = hu + ad[..., hg.shape[-1]:]
    h = activate(hg, hu, activation)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# ---------------------------------------------------------------------------
# mixture of experts (capacity-factor, static shapes)


def moe_spec(d_model: int, m: MoEConfig, activation: str,
             dtype: str) -> dict:
    E, f = m.num_experts, m.expert_ff
    p = {
        "router": Par((d_model, E), ("embed", None), init="scaled",
                      dtype="float32"),
        "we_gate": Par((E, d_model, f), ("experts", "expert_ff", None),
                       init="scaled", dtype=dtype),
        "we_down": Par((E, f, d_model), ("experts", None, "expert_ff"),
                       init="scaled", dtype=dtype),
    }
    if is_gated(activation):
        p["we_up"] = Par((E, d_model, f), ("experts", "expert_ff", None),
                         init="scaled", dtype=dtype)
    if m.shared_expert_ff:
        p["shared"] = dense_ffn_spec(d_model, m.shared_expert_ff, activation,
                                     dtype)
    return p


def _topk_dispatch(gates: jax.Array, top_k: int, capacity: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """Build combine [G,S,E,C] (fp32 weights) and dispatch (same support,
    value 1.0) from router probabilities ``gates`` [G,S,E].

    Classic GShard position assignment: experts fill in slot order; a
    token whose expert is full in slot j is dropped for that slot.
    """
    G, S, E = gates.shape
    top_vals, top_idx = jax.lax.top_k(gates, top_k)       # [G,S,K]
    counts = jnp.zeros((G, E), jnp.int32)
    combine = jnp.zeros((G, S, E, capacity), jnp.float32)
    for j in range(top_k):
        oh = jax.nn.one_hot(top_idx[..., j], E, dtype=jnp.int32)  # [G,S,E]
        pos = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]    # [G,S,E]
        pos_j = jnp.sum(pos * oh, axis=-1)                        # [G,S]
        keep = pos_j < capacity
        counts = counts + jnp.sum(oh, axis=1)
        pos_oh = jax.nn.one_hot(pos_j, capacity, dtype=jnp.float32)
        w = jnp.where(keep, top_vals[..., j], 0.0)
        combine = combine + (w[..., None, None]
                             * oh.astype(jnp.float32)[..., None]
                             * pos_oh[..., None, :])
    dispatch = (combine > 0).astype(gates.dtype)
    return combine, dispatch


def _gather_dispatch(xg, gates, m: MoEConfig, C: int):
    """Sort/gather-based static-capacity dispatch: identical routing
    semantics to the GShard einsum form but with O(tokens*d) data
    movement instead of O(tokens*E*C*d) dispatch-matmul FLOPs (a §Perf
    optimization; the einsum form is the paper-faithful baseline)."""
    G, S, E = gates.shape
    d = xg.shape[-1]
    K = m.top_k
    top_vals, top_idx = jax.lax.top_k(gates, K)               # [G,S,K]
    slot_expert = top_idx.reshape(G, S * K)                   # [G,N]
    slot_token = jnp.broadcast_to(
        jnp.arange(S)[:, None], (S, K)).reshape(S * K)
    slot_gate = top_vals.reshape(G, S * K).astype(jnp.float32)

    order = jnp.argsort(slot_expert, axis=1, stable=True)     # [G,N]
    sorted_e = jnp.take_along_axis(slot_expert, order, axis=1)
    sorted_t = slot_token[order]                              # [G,N]
    sorted_g = jnp.take_along_axis(slot_gate, order, axis=1)

    # position within the expert's run = index - start of the run
    counts = jnp.sum(jax.nn.one_hot(slot_expert, E, dtype=jnp.int32),
                     axis=1)                                   # [G,E]
    starts = jnp.cumsum(counts, axis=1) - counts               # [G,E]
    iota = jnp.broadcast_to(jnp.arange(S * K), (G, S * K))
    pos = iota - jnp.take_along_axis(starts, sorted_e, axis=1)
    keep = pos < C
    dest = jnp.where(keep, sorted_e * C + pos, E * C)          # drop slot

    xt = jnp.take_along_axis(
        xg, sorted_t[..., None].astype(jnp.int32), axis=1)     # [G,N,d]
    buf = jnp.zeros((G, E * C + 1, d), xg.dtype)
    buf = buf.at[jnp.arange(G)[:, None], dest].add(
        jnp.where(keep[..., None], xt, 0))
    xe = buf[:, :-1].reshape(G, E, C, d)
    return xe, (dest, sorted_t, sorted_g, keep)


def _gather_combine(ye, route, G, S, d):
    dest, sorted_t, sorted_g, keep = route
    E, C = ye.shape[1], ye.shape[2]
    flat = jnp.concatenate(
        [ye.reshape(G, E * C, d),
         jnp.zeros((G, 1, d), ye.dtype)], axis=1)
    out_slot = jnp.take_along_axis(
        flat, dest[..., None].astype(jnp.int32), axis=1)       # [G,N,d]
    w = (sorted_g * keep).astype(ye.dtype)[..., None]
    y = jnp.zeros((G, S, d), ye.dtype)
    y = y.at[jnp.arange(G)[:, None], sorted_t].add(out_slot * w)
    return y


def moe_ffn_ep(p: dict, x: jax.Array, m: MoEConfig, activation: str,
               x_sharding) -> jax.Array:
    """Explicit expert parallelism via shard_map — the MultiVic
    dataflow at mesh scale: expert weights stay STATIONARY in their
    2D shards (the paper's B blocks pinned in scratchpads) and the
    small thing — capacity-bounded token buffers — moves on a static
    all_to_all schedule.  The per-shard capacity is the compile-time
    worst-case assumption for dynamic routing (paper §3).

    x_sharding: the residual stream's NamedSharding (mesh + batch axes).
    """
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    mesh = x_sharding.mesh
    batch_spec = (x_sharding.spec[0] if len(x_sharding.spec) else None)
    model_n = int(mesh.shape.get("model", 1))
    data_ax = "data" if "data" in mesh.axis_names else None
    B, S, d = x.shape
    E = m.num_experts
    assert E % model_n == 0, (E, model_n)
    # shard the token (seq) dim over "model" for dispatch if divisible
    seq_ax = "model" if (model_n > 1 and S % model_n == 0) else None
    model_ax = "model" if model_n > 1 else None
    has_up = "we_up" in p

    in_x = P(batch_spec, seq_ax, None)
    w_gd = P(model_ax, data_ax, None)
    w_df = P(model_ax, None, data_ax)

    data_n = int(mesh.shape.get("data", 1)) if data_ax else 1

    def local_fn(xl, router, *ws):
        wg, wd = (ws[0], ws[2]) if has_up else (ws[0], ws[1])
        wu = ws[1] if has_up else None
        bl, sl, _ = xl.shape
        N = bl * sl
        xf = xl.reshape(1, N, d)
        logits = jnp.einsum("gnd,de->gne", xf.astype(jnp.float32),
                            router)
        gates = jax.nn.softmax(logits, axis=-1)
        C = m.capacity(N)
        xe, route = _gather_dispatch(xf, gates, m, C)
        buf = xe[0]                                     # [E, C, d]
        if model_ax and seq_ax:
            # tokens -> expert owners; experts stay put
            buf = jax.lax.all_to_all(buf, "model", split_axis=0,
                                     concat_axis=1, tiled=True)
            # [E_local, model_n*C, d]
        elif model_ax:
            # tokens replicated over "model" (e.g. decode): each shard
            # computes its local expert slice; results psum'd below.
            lo = jax.lax.axis_index("model") * (E // model_n)
            buf = jax.lax.dynamic_slice_in_dim(buf, lo, E // model_n, 0)
        # Gather this layer's d-slices of the LOCAL experts (the
        # double-buffered analogue of the paper's per-round B-block
        # DMA).  A psum-of-partials scheme that avoids this gather was
        # tried and refuted: it moves O(tokens_received * d_ff) bytes,
        # which exceeds the weight shard for both assigned MoE archs
        # (see EXPERIMENTS.md §Perf iteration log).
        if data_n > 1:
            wg = jax.lax.all_gather(wg, data_ax, axis=1, tiled=True)
            if wu is not None:
                wu = jax.lax.all_gather(wu, data_ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, data_ax, axis=2, tiled=True)
        hg = jnp.einsum("ecd,edf->ecf", buf, wg)
        hu = (jnp.einsum("ecd,edf->ecf", buf, wu)
              if wu is not None else None)
        h = activate(hg, hu, activation)
        ye = jnp.einsum("ecf,efd->ecd", h, wd)
        if model_ax and seq_ax:
            ye = jax.lax.all_to_all(ye, "model", split_axis=1,
                                    concat_axis=0, tiled=True)
        elif model_ax:
            lo = jax.lax.axis_index("model") * (E // model_n)
            full = jnp.zeros((E,) + ye.shape[1:], ye.dtype)
            ye = jax.lax.dynamic_update_slice_in_dim(full, ye, lo, 0)
        y = _gather_combine(ye[None], route, 1, N, d)
        y = y.reshape(bl, sl, d)
        if model_ax and not seq_ax:
            y = jax.lax.psum(y, "model")
        return y

    ws = (p["we_gate"], p["we_up"], p["we_down"]) if has_up \
        else (p["we_gate"], p["we_down"])
    wspecs = (w_gd, w_gd, w_df) if has_up else (w_gd, w_df)
    fn = shard_map(local_fn, mesh, (in_x, P(None, None)) + wspecs,
                   in_x)
    return fn(x, p["router"], *ws)


@jax.named_scope(FFN)
def moe_ffn(p: dict, x: jax.Array, m: MoEConfig, activation: str,
            impl: str = "einsum", x_sharding=None
            ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y, aux_loss).  Static shapes throughout.
    impl: "einsum" (GShard-faithful baseline) | "gather" (optimized)."""
    B, S, d = x.shape
    tokens = B * S
    gs = min(m.group_size, tokens)
    while tokens % gs:          # largest divisor <= group_size (static)
        gs -= 1
    G = tokens // gs
    C = m.capacity(gs)
    xg = x.reshape(G, gs, d)

    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)                   # fp32

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(gates, axis=1)                               # [G,E]
    top1 = jax.nn.one_hot(jnp.argmax(gates, -1), m.num_experts,
                          dtype=jnp.float32)
    ce = jnp.mean(top1, axis=1)                                # [G,E]
    aux = m.num_experts * jnp.mean(jnp.sum(me * ce, axis=-1))

    if impl == "ep" and x_sharding is not None:
        y = moe_ffn_ep(p, x, m, activation, x_sharding).reshape(G, gs, d)
    elif impl in ("gather", "ep"):      # "ep" without mesh -> gather
        xe, route = _gather_dispatch(xg, gates, m, C)
        hg = jnp.einsum("gecd,edf->gecf", xe, p["we_gate"])
        hu = (jnp.einsum("gecd,edf->gecf", xe, p["we_up"])
              if "we_up" in p else None)
        h = activate(hg, hu, activation)
        ye = jnp.einsum("gecf,efd->gecd", h, p["we_down"])
        y = _gather_combine(ye, route, G, gs, d)
    else:
        combine, dispatch = _topk_dispatch(gates, m.top_k, C)
        xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)
        hg = jnp.einsum("gecd,edf->gecf", xe, p["we_gate"])
        hu = (jnp.einsum("gecd,edf->gecf", xe, p["we_up"])
              if "we_up" in p else None)
        h = activate(hg, hu, activation)
        ye = jnp.einsum("gecf,efd->gecd", h, p["we_down"])
        y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)

    if "shared" in p:
        y = y + dense_ffn(p["shared"], xg, activation)
    return y.reshape(B, S, d), aux
