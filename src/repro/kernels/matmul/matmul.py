"""Tiled matmul Pallas kernel — the paper's no-collectives control benchmark.

``matmul`` in Figure 5 exercises pure serialization overhead: it has no
warp-level functions, so the SW path's only cost is the loop-serialized
execution.  Here the HW path is an MXU-tiled kernel (128-aligned blocks,
fp32 accumulation in VMEM scratch across the K grid axis); the SW comparison
in the benchmark is a serialized dot (lax.map over rows).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _matmul_kernel(a_ref, b_ref, o_ref, acc_scr, *, k_steps: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _done():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def matmul(a: jnp.ndarray, b: jnp.ndarray, *, block_m: int = 256,
           block_n: int = 256, block_k: int = 512,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    from repro.kernels.common import default_interpret

    if interpret is None:
        interpret = default_interpret()
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    block_m, block_n, block_k = min(block_m, m), min(block_n, n), min(block_k, k)
    k_steps = pl.cdiv(k, block_k)
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n), k_steps)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
