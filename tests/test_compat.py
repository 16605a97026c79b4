"""Unit tests for the repro.compat seam against the installed JAX.

Note: raw symbol names never appear literally — the compat-import lint
(scripts/check_compat_imports.py) greps for their spellings.
"""
from repro import compat


# ------------------------------------------------ compiler params class

def test_tpu_compiler_params_real_jax():
    p = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert tuple(p.dimension_semantics) == ("parallel", "arbitrary")


def test_tpu_compiler_params_drops_unknown_fields():
    p = compat.tpu_compiler_params(
        dimension_semantics=("arbitrary",),
        some_future_field_this_jax_lacks=123)
    assert tuple(p.dimension_semantics) == ("arbitrary",)


# ------------------------------------------------------ mesh / AxisType

def test_axis_type_has_auto():
    assert hasattr(compat.AxisType, "Auto")
    assert compat.auto_axis_types(3) == (compat.AxisType.Auto,) * 3


def test_mesh_kwargs_new_signature_passes_axis_types():
    types_ = compat.auto_axis_types(2)
    assert compat._mesh_kwargs(types_, None) == {"axis_types": types_}
    assert compat._mesh_kwargs(None, None) == {}


def test_make_mesh_real_jax_single_device():
    mesh = compat.make_mesh((1, 1), ("data", "model"),
                            axis_types=compat.auto_axis_types(2))
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape == {"data": 1, "model": 1}


# -------------------------------------------------------- cost analysis

def test_normalize_cost_analysis_new_dict_shape():
    ca = compat.normalize_cost_analysis({"flops": 7, "transcendentals": 1})
    assert ca == {"flops": 7.0, "transcendentals": 1.0}


def test_normalize_cost_analysis_degenerate():
    assert compat.normalize_cost_analysis(None) == {}
    assert compat.normalize_cost_analysis([]) == {}
    assert compat.normalize_cost_analysis({"weird": object()}) == {}


def test_cost_analysis_real_compiled_program():
    import jax
    import jax.numpy as jnp
    c = jax.jit(lambda x: (x @ x).sum()).lower(
        jax.ShapeDtypeStruct((16, 16), jnp.float32)).compile()
    ca = compat.cost_analysis(c)
    assert ca.get("flops", 0.0) > 0.0


# ---------------------------------------------------- interpret select

def test_resolve_interpret_explicit_passthrough():
    assert compat.resolve_interpret(True) is True
    assert compat.resolve_interpret(False) is False


def test_resolve_interpret_auto_off_tpu(monkeypatch):
    """Auto-select interprets on the CPU only: compile on a TPU, and
    refuse any other backend instead of falling back."""
    import pytest
    monkeypatch.setattr(compat.jax, "default_backend", lambda: "cpu")
    assert compat.resolve_interpret(None) is True
    monkeypatch.setattr(compat.jax, "default_backend", lambda: "tpu")
    assert compat.resolve_interpret(None) is False
    monkeypatch.setattr(compat.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        compat.resolve_interpret(None)
    assert compat.resolve_interpret(True) is True


# ----------------------------------------------------------- shard_map

def test_shard_map_kwargs_new_layout():
    kw = compat._shard_map_kwargs(check=False,
                                  auto=frozenset({"data"}),
                                  axis_names=("pod", "data"))
    assert kw == {"check_vma": False, "axis_names": {"pod"}}


def test_shard_map_real_jax_runs():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("data",))
    fn = compat.shard_map(lambda x: x * 2, mesh, (P("data"),),
                          P("data"))
    out = jax.jit(fn)(jnp.arange(4.0))
    assert jnp.allclose(out, jnp.arange(4.0) * 2)
