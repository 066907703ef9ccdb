"""Grouped matmul over the experts a chip holds: the installed Pallas
megablox kernel (``jax.experimental.pallas.ops.tpu.megablox``).

Rows of ``lhs`` come sorted by group; group ``g`` owns the next
``group_sizes[g]`` rows and multiplies them by ``rhs[g]``.  The grid
visits only the ``TM``-row tiles that hold a group's rows, so an expert
with no rows costs nothing and an expert with a few rows reads its
weights once (twice where its rows straddle a tile edge).  Rows past
``sum(group_sizes)`` belong to no group: the kernel never writes them,
and their values are unspecified.

Tiles: ``TM`` rows; along k and n the largest multiple of 128 up to
1024 that divides the width (Qwen2-57B-A14B's expert: 896 x 640 for the
gate and up projections, 640 x 896 for the down projection), so a
weight tile moves about a megabyte a grid step.
"""

from __future__ import annotations

import importlib
from typing import Optional

import jax.numpy as jnp

from repro.kernels.common import pick_block

# the kernel module (the package's ``gmm`` name is the custom-vjp op)
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

TM = 128            # rows a tile; lhs is padded to a multiple
TILE_KN = 1024      # largest k / n tile


def tiling(k: int, n: int):
    return TM, pick_block(k, TILE_KN), pick_block(n, TILE_KN)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, layer=None, *,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """lhs (m, k), rhs (G, k, n) — or a stack (L, G, k, n) read at
    ``layer`` — group_sizes (G,) int32 summing to at most m.  Returns
    (m, n) in ``lhs.dtype``, float32 accumulation.

    A stack is read where it lives: its leading axes merge (a bitcast)
    and the kernel's group offset points at the layer's groups, since
    a slice of the stack handed to a kernel would be a copy of it."""
    from repro.kernels.common import default_interpret

    if interpret is None:
        interpret = default_interpret()
    m, k = lhs.shape
    n_groups = group_sizes.shape[0]
    offset = None
    if layer is not None:
        rhs = rhs.reshape((-1,) + rhs.shape[2:])
        # the kernel reads group g's matrix at g - offset
        offset = jnp.asarray(-n_groups * layer, jnp.int32)
    pad = -m % TM
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    # the undecorated kernel function, so that the caller's jitted
    # wrapper names the op on the chip's trace
    out = _megablox.gmm.__wrapped__(
        lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
        preferred_element_type=lhs.dtype, tiling=tiling(k, rhs.shape[2]),
        group_offset=offset, interpret=interpret)
    return out[:m]
