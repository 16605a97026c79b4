"""Property tests on model components (hypothesis where useful):
RoPE shift structure, sliding-window mask semantics, spec/cache
consistency across every assigned architecture, SSD chunk invariance."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install repro[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from conftest import TINY_LAYERS, tiny_cfg
from repro.configs.all_archs import ALL_ARCH_IDS
from repro.models import cache_spec, model_spec
from repro.models.attention import sdpa
from repro.models.common import apply_rope
from repro.models.spec import is_par


# ------------------------------------------------------------------ rope

def test_rope_preserves_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 64))
    pos = jnp.arange(8)
    y = apply_rope(x, pos, 10_000.0)
    nx = jnp.linalg.norm(x, axis=-1)
    ny = jnp.linalg.norm(y, axis=-1)
    assert float(jnp.max(jnp.abs(nx - ny))) < 1e-4


def test_rope_relative_property():
    """<rope(q,i), rope(k,j)> depends only on i-j."""
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 64))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 64))

    def score(i, j):
        qr = apply_rope(q, jnp.array([i]), 10_000.0)
        kr = apply_rope(k, jnp.array([j]), 10_000.0)
        return float(jnp.sum(qr * kr))

    assert score(5, 3) == pytest.approx(score(105, 103), rel=1e-4)
    assert score(7, 0) == pytest.approx(score(57, 50), rel=1e-4)


# ------------------------------------------------------------ attn masks

@given(w=st.sampled_from([64, 128, 1 << 20]))
@settings(max_examples=6, deadline=None)
def test_window_geq_seq_equals_full(w):
    S = 64
    ks = jax.random.split(jax.random.PRNGKey(w), 3)
    q = jax.random.normal(ks[0], (1, S, 4, 32))
    k = jax.random.normal(ks[1], (1, S, 2, 32))
    v = jax.random.normal(ks[2], (1, S, 2, 32))
    pos = jnp.arange(S)
    full = sdpa(q, k, v, pos, pos, causal=True, window=0, scale=0.2,
                chunk_q=0, chunk_kv=0)
    win = sdpa(q, k, v, pos, pos, causal=True, window=w, scale=0.2,
               chunk_q=0, chunk_kv=0)
    if w >= S:
        assert float(jnp.max(jnp.abs(full - win))) < 1e-5
    else:
        assert float(jnp.max(jnp.abs(full - win))) > 1e-4


def test_chunked_equals_single_block():
    S = 64
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 32))
    k = jax.random.normal(ks[1], (2, S, 2, 32))
    v = jax.random.normal(ks[2], (2, S, 2, 32))
    pos = jnp.arange(S)
    a = sdpa(q, k, v, pos, pos, causal=True, window=48, scale=0.18,
             chunk_q=0, chunk_kv=0)
    b = sdpa(q, k, v, pos, pos, causal=True, window=48, scale=0.18,
             chunk_q=16, chunk_kv=16)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5


# ------------------------------------------- spec consistency, all archs

@pytest.mark.parametrize("arch", ALL_ARCH_IDS)
def test_model_and_cache_specs_consistent(arch):
    cfg = tiny_cfg(arch, num_layers=TINY_LAYERS[arch])
    spec = model_spec(cfg)
    leaves = jax.tree.leaves(spec, is_leaf=is_par)
    assert leaves, arch
    for p in leaves:
        assert len(p.shape) == len(p.axes)
        assert all(d > 0 for d in p.shape)
    cspec = cache_spec(cfg, batch=2, cache_len=32)
    for p in jax.tree.leaves(cspec, is_leaf=is_par):
        assert p.axes[0] == "stack"          # scan-stacked
        assert "batch" in p.axes             # every cache leaf is per-seq


@pytest.mark.parametrize("arch", ALL_ARCH_IDS)
def test_layer_counts_match_config(arch):
    from repro.models.blocks import build_stages
    cfg = tiny_cfg(arch, num_layers=TINY_LAYERS[arch])
    n = sum(st_.n_units * st_.unit_len for st_ in build_stages(cfg))
    assert n == cfg.num_layers, (arch, n, cfg.num_layers)


# ------------------------------------------------------------- ssd chunks

def test_ssd_chunk_size_invariance():
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N = 1, 64, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (B, S, H, P))
    a = -jnp.abs(jax.random.normal(ks[1], (B, S, H))) * 0.1
    Bm = jax.random.normal(ks[2], (B, S, 1, N))   # one B/C group
    Cm = jax.random.normal(ks[3], (B, S, 1, N))
    y16, s16 = ssd_chunked(x, a, Bm, Cm, 16)
    y64, s64 = ssd_chunked(x, a, Bm, Cm, 64)
    assert float(jnp.max(jnp.abs(y16 - y64))) < 1e-4
    assert float(jnp.max(jnp.abs(s16 - s64))) < 1e-4