"""Parameter-spec system: a single source of truth from which we derive
(a) randomly initialized parameter pytrees (smoke tests / examples),
(b) ShapeDtypeStructs with shardings (multi-pod dry-run, no allocation),
(c) PartitionSpec trees (pjit in/out shardings).

A leaf is a ``Par``: shape + logical axes + init style.  Builders in the
model modules compose nested dicts of Par; ``stack`` prepends the scan
("stack") dimension for repeated layers.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.rules import ShardingRules


@dataclass(frozen=True)
class Par:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones | scaled | decay
    scale: float = 0.02
    dtype: str = "bfloat16"
    # contracted size for init="scaled"; 0 = shape[-2], the input dim
    # of a [..., in, out] matrix.  Head-split projections set it.
    fan_in: int = 0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_par(x) -> bool:
    return isinstance(x, Par)


def stack(tree, n: int):
    """Prepend a scan/stack dimension of size n to every Par in tree."""
    return jax.tree.map(
        lambda p: replace(p, shape=(n,) + p.shape, axes=("stack",) + p.axes),
        tree, is_leaf=is_par)


def cast(tree, dtype: str):
    return jax.tree.map(lambda p: replace(p, dtype=dtype), tree,
                        is_leaf=is_par)


# ---------------------------------------------------------------------------
# realizations


def _init_leaf(p: Par, key) -> jax.Array:
    dt = jnp.dtype(p.dtype)
    if p.init == "zeros":
        return jnp.zeros(p.shape, dt)
    if p.init == "ones":
        return jnp.ones(p.shape, dt)
    if p.init == "decay":
        # small negative values; used for SSM/RWKV decay parameters
        return jnp.asarray(
            -0.5 - 2.0 * jax.random.uniform(key, p.shape), dt)
    scale = p.scale
    if p.init == "scaled":
        fan_in = p.fan_in or (p.shape[-2] if len(p.shape) >= 2
                              else p.shape[-1])
        scale = 1.0 / np.sqrt(max(1, fan_in))
    return jnp.asarray(scale * jax.random.normal(key, p.shape, jnp.float32),
                       dt)


def init_tree(tree, key) -> dict:
    """Materialize random parameters for a spec tree."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_par)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(p, k) for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def shape_tree(tree, rules: Optional[ShardingRules] = None) -> dict:
    """ShapeDtypeStructs (with shardings if rules given) — used by the
    dry-run so no memory is ever allocated for the full-size models."""
    def f(p: Par):
        if rules is None:
            return jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype))
        return jax.ShapeDtypeStruct(
            p.shape, jnp.dtype(p.dtype),
            sharding=rules.sharding_for(p.axes, p.shape))
    return jax.tree.map(f, tree, is_leaf=is_par)


def pspec_tree(tree, rules: ShardingRules):
    return jax.tree.map(lambda p: rules.spec_for(p.axes, p.shape), tree,
                        is_leaf=is_par)


def param_bytes(tree) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=is_par)
    return int(sum(np.prod(p.shape, dtype=np.int64) *
                   jnp.dtype(p.dtype).itemsize for p in leaves))


def param_count(tree) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=is_par)
    return int(sum(np.prod(p.shape, dtype=np.int64) for p in leaves))
