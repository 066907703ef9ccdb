"""Matrix products of the plain references, and the float8 control.

A family's reference computes every matrix product in float32 at
``precision="highest"``: on a TPU a float32 product is otherwise
computed in bfloat16 passes.  The control computes the same products
with their inputs rounded to float8 (e4m3, one scale per tensor), the
precision one step below the configurations' bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def fp8(x, amax=None):
    """Round to float8 e4m3 with one scale for the tensor (or for the
    whole tensor ``x`` is a block of, given its ``amax``)."""
    if amax is None:
        amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(x, w, control: bool):
    """``x @ w`` in float32, or with float8 inputs for the control."""
    if control:
        x, w = fp8(x), fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)
