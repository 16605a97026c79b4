"""Single seam for every JAX surface outside the stable core API.

The repo targets exactly the installed JAX (0.9.0).  The paper's
predictability story (PAPER.md §III: one statically-known substrate,
identical behaviour everywhere) forbids scattering raw
``jax.experimental`` and sharding-internals references through kernels
and launch code, so they all live here:

  * Pallas TPU compiler params -> ``tpu_compiler_params()``.
  * ``jax.sharding.AxisType`` / ``jax.make_mesh(axis_types=...)`` ->
    ``AxisType`` + ``make_mesh()``.
  * ``Compiled.cost_analysis()`` (a flat dict, or ``None`` on some
    backends) -> ``cost_analysis()`` / ``normalize_cost_analysis()``.
  * ``jax.shard_map`` with ``check_vma``/``axis_names`` -> ``shard_map()``.
  * Pallas interpret-mode selection on the CPU -> ``resolve_interpret()``.
  * Described TPU topologies (compile without a chip) -> ``tpu_topology()``.
  * Array layout constraints (``jax.experimental.layout``) ->
    ``with_row_major_layout()``.

Policy (enforced by scripts/check_compat_imports.py, run as a tier-1
test): no module outside this file may reference the raw symbols
directly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax

__all__ = [
    "tpu_compiler_params",
    "AxisType",
    "auto_axis_types",
    "make_mesh",
    "cost_analysis",
    "normalize_cost_analysis",
    "resolve_interpret",
    "shard_map",
    "donated_jit",
    "aot_compile",
    "tpu_topology",
    "with_row_major_layout",
]


# --------------------------------------------------- Pallas TPU params

def tpu_compiler_params(*, dimension_semantics: Optional[Sequence[str]]
                        = None, **kwargs) -> Any:
    """Construct Pallas TPU compiler params.

    Unknown fields are dropped (not an error): a field this JAX doesn't
    know is a hint it cannot honour, never a hard failure.
    """
    from jax.experimental.pallas import tpu as pltpu
    cls = pltpu.CompilerParams
    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = tuple(dimension_semantics)
    accepted = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in kwargs.items() if k in accepted})


# ------------------------------------------------------ mesh / AxisType

AxisType = jax.sharding.AxisType


def auto_axis_types(n: int) -> Tuple[Any, ...]:
    """``(AxisType.Auto,) * n``."""
    return (AxisType.Auto,) * n


def _mesh_kwargs(axis_types, devices) -> Dict:
    """Only the requested fields: an unset ``axis_types`` keeps
    ``jax.make_mesh``'s own default."""
    kw: Dict[str, Any] = {}
    if devices is not None:
        kw["devices"] = devices
    if axis_types is not None:
        kw["axis_types"] = axis_types
    return kw


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types=None, devices=None):
    """``jax.make_mesh`` passing only the fields the caller set."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         **_mesh_kwargs(axis_types, devices))


# -------------------------------------------------------- cost analysis

def normalize_cost_analysis(raw) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as one str->float dict: numeric
    entries only, and ``{}`` where the backend reports nothing."""
    if not isinstance(raw, Mapping):
        return {}
    out = {}
    for k, v in raw.items():
        try:
            out[str(k)] = float(v)
        except (TypeError, ValueError):
            continue
    return out


def cost_analysis(compiled) -> Dict[str, float]:
    """Normalized cost analysis of a compiled executable."""
    return normalize_cost_analysis(compiled.cost_analysis())


# ------------------------------------------------- donation / AOT jit

def donated_jit(fn, *, donate_argnums: Tuple[int, ...] = (),
                static_argnums: Tuple[int, ...] = (),
                platform: Optional[str] = None):
    """``jax.jit`` with buffer donation, requested only where the
    backend honours it.

    Donation is the serving steady state's realloc killer (the KV cache
    is updated in place instead of copied every decode step), but CPU —
    the validation backend — implements it only partially and warns on
    every compile.  Requesting donation only where it works keeps the
    timed region identical across backends without drowning CPU runs in
    warnings; the *semantics* (caller must not reuse donated args) are
    the same either way, so code tested on CPU is donation-correct on
    TPU.  ``platform`` is the one the program is compiled for, where
    that is not the default backend's (a described chip).
    """
    if (platform or jax.default_backend()) not in ("tpu", "gpu"):
        donate_argnums = ()
    return jax.jit(fn, donate_argnums=donate_argnums,
                   static_argnums=static_argnums)


def aot_compile(jitted, *args, **kwargs):
    """Ahead-of-time compile a jitted callable for example arguments.

    The returned executable runs with ZERO compile-time jitter — the
    serving loop compiles before its timed region starts.
    """
    return jitted.lower(*args, **kwargs).compile()


def with_row_major_layout(x):
    """``x`` held in the row-major layout (with the TPU's own tiling),
    the one a TPU program takes and returns arrays in by default, where
    the program is lowered for a TPU; elsewhere ``x`` as it is."""
    from jax.experimental.layout import Layout, with_layout_constraint
    layout = Layout(major_to_minor=tuple(range(x.ndim)))
    return jax.lax.platform_dependent(
        x, tpu=lambda y: with_layout_constraint(y, layout),
        default=lambda y: y)


def tpu_topology(name: str):
    """A described (not attached) TPU topology, e.g. ``"v5e:2x2"``: its
    ``devices`` take shardings, so programs compile for that chip on a
    host without one.  Raises where the TPU compiler is not installed."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu", topology_name=name)


# ---------------------------------------------------- interpret select

def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Kernel entry points take ``interpret=None`` = auto: compile on
    TPU, interpret on the CPU validation backend.  Any other backend
    raises: a Pallas TPU kernel has no silent fallback there."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on backend {backend!r}; pass "
        "interpret=True explicitly to interpret them there")


# ----------------------------------------------------------- shard_map

def _shard_map_kwargs(*, check: bool, auto: frozenset,
                      axis_names: Sequence[str]) -> Dict:
    """``auto`` (the mesh axes left to GSPMD) becomes shard_map's
    complementary ``axis_names`` (the manual axes)."""
    kw: Dict[str, Any] = {"check_vma": check}
    if auto:
        kw["axis_names"] = set(axis_names) - set(auto)
    return kw


def shard_map(f, mesh, in_specs, out_specs, *, check: bool = False,
              auto: frozenset = frozenset()):
    """``jax.shard_map`` with ``check`` -> ``check_vma`` and ``auto``
    translated to ``axis_names``."""
    kw = _shard_map_kwargs(check=check, auto=auto,
                           axis_names=mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
