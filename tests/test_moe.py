"""Capacity-factor MoE properties — the paper's 'static assumptions for
dynamic behaviour' must hold structurally."""
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install repro[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.base import MoEConfig
from repro.models.ffn import _topk_dispatch, moe_ffn, moe_spec
from repro.models.spec import init_tree


@given(seed=st.integers(0, 1000),
       gs=st.sampled_from([16, 32]),
       E=st.sampled_from([4, 8]),
       k=st.sampled_from([1, 2]))
@settings(max_examples=20, deadline=None)
def test_dispatch_respects_capacity(seed, gs, E, k):
    key = jax.random.PRNGKey(seed)
    gates = jax.nn.softmax(jax.random.normal(key, (2, gs, E)), -1)
    C = max(2, gs * k // E)
    combine, dispatch = _topk_dispatch(gates, k, C)
    # at most one token per (expert, slot)
    per_slot = dispatch.sum(axis=1)            # [G, E, C]
    assert float(per_slot.max()) <= 1.0 + 1e-6
    # each token routed to at most k slots
    per_token = dispatch.sum(axis=(2, 3))      # [G, S]
    assert float(per_token.max()) <= k + 1e-6
    # combine weights are within the gate simplex
    assert float(combine.sum(axis=(2, 3)).max()) <= 1.0 + 1e-5


def test_moe_static_shapes_and_aux():
    m = MoEConfig(num_experts=4, top_k=2, expert_ff=32, group_size=16,
                  capacity_factor=2.0)
    p = init_tree(moe_spec(64, m, "swiglu", "float32"),
                  jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    y, aux = moe_ffn(p, x, m, "swiglu")
    assert y.shape == x.shape
    assert jnp.isfinite(aux)
    # aux loss is ~1 for a balanced uniform router
    assert 0.5 < float(aux) < 4.0


def test_moe_deterministic():
    m = MoEConfig(num_experts=4, top_k=1, expert_ff=16, group_size=8)
    p = init_tree(moe_spec(32, m, "gelu", "float32"),
                  jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 32))
    y1, _ = moe_ffn(p, x, m, "gelu")
    y2, _ = moe_ffn(p, x, m, "gelu")
    assert jnp.array_equal(y1, y2)   # input-independent static schedule


def test_ep_matches_einsum_single_device():
    """shard_map expert parallelism == einsum dispatch (1x1 mesh)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    xs = NamedSharding(mesh, P("data", None, None))
    m = MoEConfig(num_experts=8, top_k=2, expert_ff=32, group_size=32,
                  capacity_factor=8.0)
    p = init_tree(moe_spec(64, m, "swiglu", "float32"),
                  jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    y1, _ = moe_ffn(p, x, m, "swiglu", "einsum")
    y2, _ = moe_ffn(p, x, m, "swiglu", "ep", xs)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-5


def test_ep_multidevice():
    """EP correctness across real shards (8 host devices, 2x4 mesh) —
    runs in a subprocess because the device count is process-global."""
    import os
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.compat import auto_axis_types, make_mesh
from repro.configs.base import MoEConfig
from repro.models.ffn import moe_ffn, moe_spec
from repro.models.spec import init_tree
mesh = make_mesh((2, 4), ("data", "model"),
                 axis_types=auto_axis_types(2))
xs = NamedSharding(mesh, P("data", None, None))
m = MoEConfig(num_experts=8, top_k=2, expert_ff=64, group_size=64,
              capacity_factor=8.0)
p = init_tree(moe_spec(64, m, "swiglu", "float32"), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 64))
y1, _ = jax.jit(lambda p, x: moe_ffn(p, x, m, "swiglu", "einsum"))(p, x)
y2, _ = jax.jit(lambda p, x: moe_ffn(p, x, m, "swiglu", "ep", xs))(
    p, jax.device_put(x, xs))
err = float(jnp.max(jnp.abs(y1 - y2)))
assert err < 2e-5, err
print("OK", err)
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=".",
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
