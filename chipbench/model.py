"""The system under test, common to every architecture: the program's
parameter tree made from a seed in one jitted call and checked against
the program's own spec.  What maps a configuration file onto the
program's ``ModelConfig`` and tree is the architecture's module
(``chipbench/reference/<name>.py``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import weights as W


def pad_vocab(t, rows: int):
    """A [vocab, d] table padded with zero rows to ``rows``."""
    return jnp.pad(t, ((0, rows - t.shape[0]), (0, 0)))


def check_tree(cfg, shapes) -> None:
    """Raise unless ``shapes`` (of a parameter tree) has exactly the
    leaves, shapes and dtypes of ``lm.model_spec(cfg)``."""
    from repro.models import lm
    from repro.models.spec import is_par
    want = jax.tree.map(lambda p: (tuple(p.shape), jnp.dtype(p.dtype)),
                        lm.model_spec(cfg), is_leaf=is_par)
    got = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)),
                       shapes)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError(f"weights do not match the program's spec for "
                         f"{cfg.name}:\n want {want}\n got  {got}")


def seeded_params(cfg, make, seed: int):
    """``make(key)``, the program's parameter tree from the key of
    ``seed``, run on the device in one jitted call, in the dtypes the
    tree is served in, after its shapes are checked against the
    program's spec."""
    fn = jax.jit(make)
    key = W.base_key(seed)
    check_tree(cfg, jax.eval_shape(fn, key))
    return fn(key)
