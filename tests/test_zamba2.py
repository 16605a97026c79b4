"""Zamba2 as published, at test size on the CPU: the grouped chunked scan
against the plain sequential recurrence, the scan's bounded temporaries,
the hybrid layers at the published ids with alternating tied blocks, and
the model served through ``launch/serve.py``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.configs import get_config
from repro.models import blocks as blk
from repro.models import lm, ssm


def _scan_inputs(key, B=2, S=64, H=8, P=16, G=2, N=8):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    a = -jax.random.uniform(ks[1], (B, S, H), minval=0.01, maxval=1.0)
    Bm = jax.random.normal(ks[2], (B, S, G, N))
    Cm = jax.random.normal(ks[3], (B, S, G, N))
    s0 = jax.random.normal(ks[4], (B, H, N, P))
    return x, a, Bm, Cm, s0


def _sequential(x, a, Bm, Cm, s0):
    """h_t = exp(a_t) h_{t-1} + B_t x_t^T, y_t = C_t . h_t, one token
    after another; head h reads group h // (H / G)."""
    H, G = x.shape[2], Bm.shape[2]
    Bh = jnp.repeat(Bm, H // G, axis=2)
    Ch = jnp.repeat(Cm, H // G, axis=2)

    def step(S, t):
        xt, at, bt, ct = t
        S = jnp.exp(at)[..., None, None] * S \
            + bt[..., :, None] * xt[..., None, :]
        return S, jnp.einsum("bhn,bhnp->bhp", ct, S,
                             precision=jax.lax.Precision.HIGHEST)

    first = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    S, y = jax.lax.scan(step, s0, tuple(map(first, (x, a, Bh, Ch))))
    return jnp.moveaxis(y, 0, 1), S


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("with_state", [False, True])
def test_grouped_chunked_scan_is_the_sequential_recurrence(chunk,
                                                           with_state):
    x, a, Bm, Cm, s0 = _scan_inputs(jax.random.PRNGKey(chunk))
    if not with_state:
        s0 = jnp.zeros_like(s0)
    y, final = ssm.ssd_chunked(x, a, Bm, Cm, chunk,
                               s0 if with_state else None)
    want_y, want_s = _sequential(x, a, Bm, Cm, s0)
    scale = float(jnp.max(jnp.abs(want_y)))
    assert float(jnp.max(jnp.abs(y - want_y))) < 1e-4 * scale
    assert float(jnp.max(jnp.abs(final - want_s))) < 1e-4 * float(
        jnp.max(jnp.abs(want_s)))


@pytest.mark.parametrize("rows", [1, 2])
def test_bounded_scan_is_the_unbounded_one(rows):
    x, a, Bm, Cm, s0 = _scan_inputs(jax.random.PRNGKey(7), B=4)
    for init in (None, s0):
        y, final = ssm.ssd_chunked(x, a, Bm, Cm, 16, init)
        yb, fb = ssm.ssd_chunked(x, a, Bm, Cm, 16, init, rows=rows)
        np.testing.assert_allclose(yb, y, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(fb, final, rtol=1e-6, atol=1e-6)


def test_scan_rows_bound_the_temporaries():
    # zamba2-7b's prefill of 64 x 512 tokens: 112 heads, chunks of 256
    rows = ssm.scan_rows(64, 512, 112, 256)
    assert 64 % rows == 0 and rows < 64
    assert rows * 512 * 256 * 112 * 6 <= ssm.SCAN_TEMP_BYTES
    assert ssm.scan_rows(2, 64, 8, 16) == 2        # small: one block
    assert ssm.scan_rows(7, 1 << 20, 64, 256) == 1


def _kinds(stages):
    out = []
    for st in stages:
        for u in range(st.n_units):
            for i, d in enumerate(st.unit):
                out.append(int(st.hybrid_ordinal(u, i)) if d.shared_attn
                           else None)
    return out


@pytest.mark.parametrize("layers,shape", [
    (81, [(6, 1), (1, 5), (11, 6), (1, 4)]),
    (21, [(6, 1), (1, 5), (1, 6), (1, 4)]),
    (6, [(6, 1)]),
])
def test_hybrid_layers_sit_at_the_published_ids(layers, shape):
    cfg = dataclasses.replace(get_config("zamba2-7b"), num_layers=layers)
    stages = blk.build_stages(cfg)
    assert [(st.n_units, st.unit_len) for st in stages] == shape
    kinds = _kinds(stages)
    assert len(kinds) == layers
    ids = [i for i in cfg.ssm.hybrid_layer_ids if i < layers]
    assert [i for i, k in enumerate(kinds) if k is not None] == ids
    # the hybrid layers in order: ordinals 0, 1, 2, ...; the tied block
    # is the ordinal mod 2, so block 0 at ids 6, 17, 29, ...
    assert [k for k in kinds if k is not None] == list(range(len(ids)))


def test_the_registry_entry_is_the_published_config():
    cfg = get_config("zamba2-7b")
    a, s = cfg.attention, cfg.ssm
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == \
        (81, 3584, 14336, 32000)
    assert (a.num_heads, a.num_kv_heads, a.head_dim, a.rope_theta) == \
        (32, 32, 224, 1e4)
    assert a.softmax_scale == pytest.approx(112 ** -0.5)
    assert (s.n_groups, s.state_dim, s.head_dim, s.expand, s.conv_kernel,
            s.chunk_size, s.n_shared_blocks, s.adapter_rank) == \
        (2, 64, 64, 2, 4, 256, 2, 128)
    assert s.hybrid_layer_ids == (6, 11, 17, 23, 29, 35, 41, 47, 53, 59,
                                  65, 71, 77)
    assert ssm.ssm_dims(cfg.d_model, s) == (7168, 112, 7424)
    assert (cfg.activation, cfg.norm_eps, cfg.tie_embeddings) == \
        ("geglu_exact", 1e-5, True)


def _tiny(layers=9, ids=(2, 5, 8)):
    cfg = tiny_cfg("zamba2-7b", num_layers=layers, dtype="float32")
    return dataclasses.replace(cfg, ssm=dataclasses.replace(
        cfg.ssm, hybrid_layer_ids=ids, adapter_rank=8, chunk_size=8))


def _run(cfg, params, toks):
    x, _, _ = lm.forward_hidden(cfg, params, {"tokens": toks},
                                lm.RunOptions(chunk_q=0, chunk_kv=0,
                                              remat=False))
    return lm.compute_logits(cfg, params, x[:, -1])


def _only(params, keep):
    """``params`` with the per-layer linear of every hybrid layer but
    ``keep`` (id 2, 5 or 8 of ``_tiny``) zeroed: only that layer's tied
    block then reaches the output."""
    p = jax.tree.map(lambda a: a, params)
    units = {2: 0, 5: 1}          # stage1's two scanned [H, M, M] units
    lin = p["stage1"]["pos0"]["linear"]
    p["stage1"]["pos0"]["linear"] = jnp.stack(
        [lin[u] * (units.get(keep) == u) for u in range(2)])
    p["stage2"]["pos0"]["linear"] = p["stage2"]["pos0"]["linear"] \
        * (keep == 8)
    return p


def test_the_tied_block_alternates_by_hybrid_ordinal():
    """Ids 2, 5, 8 take blocks 0, 1, 0: 2 and 5 share one scanned stage
    (a traced ordinal), 8 is a stage of its own (a static one).  Zeroing
    a block's output projection moves what a hybrid layer adds iff the
    layer runs that block."""
    cfg = _tiny()
    stages = blk.build_stages(cfg)
    assert [(s.n_units, s.unit_len, s.hybrid0) for s in stages] == \
        [(2, 1, 0), (2, 3, 0), (1, 1, 2)]
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    for layer_id, block in ((2, 0), (5, 1), (8, 0)):
        p = _only(params, layer_id)
        one = _run(cfg, p, toks)
        for b in (0, 1):
            q = jax.tree.map(lambda a: a, p)
            q["shared"]["attn"]["wo"] = q["shared"]["attn"]["wo"].at[b].set(0)
            moved = float(jnp.max(jnp.abs(_run(cfg, q, toks) - one)))
            assert (moved > 0) == (b == block), (layer_id, b, moved)


def test_the_block_adds_to_the_mamba_input_not_the_residual():
    """With every Mamba2 output projection zeroed, a hybrid layer adds
    nothing to the residual stream: its block's output only feeds its
    Mamba2 layer."""
    cfg = _tiny()
    params = lm.init_params(cfg, jax.random.PRNGKey(2))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if "out_proj" in jax.tree_util.keystr(path) else a, params)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                              cfg.vocab_size)
    x, _, _ = lm.forward_hidden(cfg, params, {"tokens": toks},
                                lm.RunOptions(chunk_q=0, chunk_kv=0,
                                              remat=False))
    from repro.models.common import rmsnorm
    x0 = jnp.take(params["embed"], toks, axis=0)
    want = rmsnorm(x0, params["final_norm"], cfg.norm_eps)
    np.testing.assert_allclose(x, want, rtol=1e-6, atol=1e-6)


def test_zamba2_serves_through_launch_serve():
    """``serve.run`` at test size with the hybrid layer at id 6 in its
    8 layers: the prompt's prefill and every decode step through the
    compiled step programs."""
    from repro.launch import serve
    out = serve.run(serve.parse_args([
        "--arch", "zamba2-7b", "--batch", "2", "--prompt-len", "32",
        "--layers", "8", "--d-model", "64", "--vocab", "256", "--gen", "3",
        "--deadline-ms", "1e6"]))
    cfg = out["cfg"]
    assert cfg.name == "zamba2-7b" and cfg.num_layers == 8
    assert [d.shared_attn for st in blk.build_stages(cfg)
            for d in st.unit] == [False, True, False]
    tokens = np.stack([out["first_token"]] + out["generated"], 1)
    assert tokens.shape == (2, 4) and np.all((0 <= tokens) & (tokens < 256))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_state_space_blocks_reach_the_compiled_programs(program):
    """The served step programs of a hybrid model name the state-space
    layer's blocks besides the transformer's: every HLO operation of
    ``ssm_proj`` and ``ssm_core`` maps to its block."""
    from repro.launch import serve
    from repro.obs import BLOCKS, SSM_BLOCKS, op_blocks
    cfg = _tiny()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    opts = lm.RunOptions(chunk_q=8, chunk_kv=8, cache_len=20, remat=False,
                         decode_scan=True)
    prefill, step, _ = serve.compile_step_fns(cfg, params, batch, opts, 16)
    compiled = (prefill if program == "prefill" else step).compiled
    assert set(op_blocks(compiled).values()) == set(BLOCKS + SSM_BLOCKS)
