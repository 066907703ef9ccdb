"""Jitted wrapper for the grouped matmul kernel.  The wrapper's name is
the kernel's op name on the chip's trace (``KERNEL_NAME``), every call
of every layer."""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.grouped_matmul.grouped_matmul import grouped_matmul

KERNEL_NAME = "grouped_matmul_op"


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul_op(lhs: jnp.ndarray, rhs: jnp.ndarray,
                      group_sizes: jnp.ndarray, layer=None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """lhs (m, k) sorted by group, rhs (G, k, n) or a stack (L, G, k, n)
    read at ``layer``, group_sizes (G,).  Returns (m, n); rows past
    ``sum(group_sizes)`` are unspecified."""
    return grouped_matmul(lhs, rhs, group_sizes, layer,
                          interpret=interpret)
