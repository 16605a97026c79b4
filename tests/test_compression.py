"""int8 cross-pod gradient compression: error bound + multi-device
mean correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install repro[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.optim.compression import quantize_roundtrip


@given(seed=st.integers(0, 1000), scale=st.floats(1e-4, 1e3))
@settings(max_examples=30, deadline=None)
def test_quantization_error_bound(seed, scale):
    g = scale * jax.random.normal(jax.random.PRNGKey(seed), (256,))
    gq = quantize_roundtrip(g)
    amax = float(jnp.max(jnp.abs(g)))
    # uniform quantizer: |err| <= step/2 = amax/127/2 (+eps)
    assert float(jnp.max(jnp.abs(gq - g))) <= amax / 127.0 / 2 + 1e-6


def test_zero_grads_stay_zero():
    g = jnp.zeros((64,))
    assert jnp.all(quantize_roundtrip(g) == 0)


def test_compressed_mean_multipod():
    """2-pod mean via the int8 wire format, on real host devices: pod 0
    holds g, pod 1 holds 3g -> compressed mean ~= 2g within the
    quantization bound."""
    import os
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.compat import auto_axis_types, make_mesh, shard_map
from repro.optim.compression import _compress_psum_leaf
mesh = make_mesh((2, 2), ("pod", "data"),
                 axis_types=auto_axis_types(2))
g = jnp.arange(-8.0, 8.0).reshape(4, 4) / 8.0
stacked = jnp.stack([g, 3 * g])                  # [pod, ...]
fn = shard_map(
    lambda x: _compress_psum_leaf(x[0], "pod")[None],
    mesh, (P("pod", None, None),),
    P("pod", None, None))
out = jax.jit(fn)(jax.device_put(
    stacked, NamedSharding(mesh, P("pod", None, None))))
# both pods now hold the (identical) compressed mean
err = float(jnp.max(jnp.abs(out[0] - 2 * g)))
assert err <= float(jnp.max(jnp.abs(3 * g))) / 127.0 + 1e-6, err
print("OK", err)
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=".",
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
