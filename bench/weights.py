"""Seeded weights, made by the benchmark: the scheme every family uses.

The benchmark makes the weights and hands them to the program, so that
the reference can make the very same values again without taking
anything from the program.  Every value is an 8-bit integer times a
power of two (norm weights: 1 plus a multiple of 2**-7), so it is exact
in bfloat16 and in float32, and the served weights and the reference's
float32 copy hold identical numbers.

Each leaf of each layer has a key of its own, ``fold(fold(base, id),
layer)``, where ``id`` is the leaf's entry in its family's table of leaf
ids, so the program's stacked leaves (made in one jitted call, on the
device, in the served type) and the reference's one-layer-at-a-time
float32 leaves come from the same bits.  A family fixes its table for
good: the ids key the random bits.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

INT8_STD = math.sqrt((256 ** 2 - 1) / 12.0)   # std of uniform bytes


def base_key(seed: int):
    """A key from any non-negative seed, all 64 bits of it counted."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64); got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def exponent(std: float) -> int:
    """Power of two that brings uniform bytes nearest to ``std``."""
    return int(round(math.log2(INT8_STD / std)))


def matrix(a: int, b: int):
    """Spec of an ``(a, b)`` projection, scaled as Glorot's."""
    return ((a, b), "int", exponent(math.sqrt(2.0 / (a + b))))


def leaf(key, ids: Dict[str, int], name: str, layer, spec, dtype):
    """One layer's value of leaf ``name``; exact in bf16 and f32.
    ``spec`` is ``(shape, kind, exponent)``, kind ``"int"`` or
    ``"norm"``; ``ids`` is the family's table of leaf ids."""
    shape, kind, e = spec
    k = jax.random.fold_in(jax.random.fold_in(key, ids[name]), layer)
    bits = jax.random.bits(k, shape, jnp.uint8)
    if kind == "norm":
        # 1 + j / 128, j in [-16, 16]
        j = (bits % 33).astype(jnp.float32) - 16.0
        return (1.0 + j * 2.0 ** -e).astype(dtype)
    x = jax.lax.bitcast_convert_type(bits, jnp.int8).astype(jnp.float32)
    return (x * 2.0 ** -e).astype(dtype)
