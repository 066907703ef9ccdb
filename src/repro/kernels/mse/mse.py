"""MSE-forward Pallas kernel — the unet.cu ``mse_forward`` microbenchmark.

The CUDA original computes per-thread squared error, then a ``shfl_down``
tree reduction per warp, and one atomic add per warp leader.  The TPU HW-path
kernel mirrors that structure: squared error in registers, shfl_down
butterfly per (block_rows, warp_size) lane group, then a grid-carried scalar
accumulation (the atomic-add analogue: the output block is revisited across
the 1-D grid with "arbitrary" semantics).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _mse_kernel(p_ref, t_ref, o_ref, *, width: int, steps: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d = p_ref[...].astype(jnp.float32) - t_ref[...].astype(jnp.float32)
    v = d * d
    # shfl_down tree: after log2(width) steps lane 0 holds the warp sum.
    lanes = jax.lax.broadcasted_iota(jnp.int32, v.shape, dimension=v.ndim - 1)
    offset = width // 2
    while offset >= 1:
        src = jnp.where(lanes + offset < width, lanes + offset, lanes)
        v = v + jnp.where(lanes + offset < width,
                          jnp.take_along_axis(v, src, axis=-1), 0.0)
        offset //= 2
    warp_sums = v[:, 0]                      # lane-0 values (warp leaders)
    o_ref[0, 0] += jnp.sum(warp_sums)        # atomic-add analogue


def mse_partial_sum(pred: jnp.ndarray, target: jnp.ndarray, *,
                    warp_size: int = 32, block_rows: int = 256,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sum of squared errors over a flat array (mean taken by the wrapper)."""
    from repro.kernels.common import default_interpret

    if interpret is None:
        interpret = default_interpret()
    n = pred.size
    assert n % warp_size == 0, "pad inputs to a warp multiple"
    rows = n // warp_size
    block_rows = min(block_rows, rows)
    steps = pl.cdiv(rows, block_rows)
    p2 = pred.reshape(rows, warp_size)
    t2 = target.reshape(rows, warp_size)
    out = pl.pallas_call(
        functools.partial(_mse_kernel, width=warp_size, steps=steps),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((block_rows, warp_size), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, warp_size), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(p2, t2)
    return out[0, 0]
