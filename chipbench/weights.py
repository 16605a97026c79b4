"""Seeded random weights, common to every architecture: the key taken
from a seed, and the generator of one leaf.

An architecture's module (``chipbench/reference/<name>.py``) names and
shapes its leaves after the published configuration, not after the
program's parameter tree, and makes each from its own key and name with
``leaf``; by convention the leaves of layer ``l`` from
``fold_in(key, l + 1)`` and the rest from ``fold_in(key, 0)``, so that
one layer can be made on its own (the reference, layer by layer) and
gives the same numbers as the vmapped whole (the program, in one call).

Values are uniform, with the standard deviations of the program's
initialisers (``models/spec.py``): 0.02 for the embedding and head,
1/sqrt(fan_in) for a projection.  The leaves the program starts at 0 or
1 (biases, norm gains) are spread around that value, so that the
comparison sees whether they are used.  Each value is made from random
bits by exact steps and one rounding, so that two programs that fuse the
arithmetic differently still make the same weights (a Gaussian's
polynomial does not promise that: one bfloat16 value in 44,032 came out
one unit apart).

This module imports nothing of the program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.1
NORM_SPAN = 0.125    # gains uniform in 1 +- NORM_SPAN (a power of two)


def base_key(seed: int) -> jax.Array:
    """A legacy threefry key from all 64 bits of ``seed`` (``PRNGKey``
    keeps only the low 32)."""
    s = int(seed) % (1 << 64)
    return jnp.array([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32)


def leaf(key, name: str, shape, dtype, init: str, scale: float):
    """The leaf ``name`` under ``key``: ``init`` ``"norm"`` is uniform in
    1 +- ``scale``, anything else uniform with standard deviation
    ``scale``; made in float32 and rounded once to ``dtype``."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    bits = jax.random.bits(k, shape, jnp.uint32) >> 9
    # (2i + 1) / 2**23 - 1, exact in float32: uniform on (-1, 1)
    u = (bits.astype(jnp.float32) * 2.0 + 1.0) * 2.0 ** -23 - 1.0
    if init == "norm":
        return (1.0 + u * scale).astype(dtype)
    return (u * (scale * 3.0 ** 0.5)).astype(dtype)


def make(key, shapes: dict) -> dict:
    """Every leaf of ``shapes`` (name -> (shape, dtype, init, scale))."""
    return {n: leaf(key, n, *spec) for n, spec in shapes.items()}


def nbytes(shapes: dict) -> int:
    """Bytes of the leaves of ``shapes`` in their dtypes."""
    return sum(int(np.prod(s)) * jnp.dtype(t).itemsize
               for s, t, _, _ in shapes.values())
