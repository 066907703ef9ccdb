"""Shared Pallas utilities: interpret-mode detection, tiling helpers.

All kernels target TPU (BlockSpec VMEM tiling, MXU-aligned shapes) and are
validated on CPU with ``interpret=True`` — the kernel body executes in Python
with identical semantics.
"""

from __future__ import annotations

import functools

import jax


@functools.cache
def default_interpret() -> bool:
    """Interpret unless a real TPU backend is present."""
    return jax.default_backend() != "tpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


# TPU hardware alignment constants (v4/v5 generation).
LANE = 128          # VPU lane width / MXU matrix dimension
SUBLANE_F32 = 8     # sublanes per VREG row, fp32
MXU = 128           # systolic array dimension


def pick_block(dim: int, preferred: int, align: int = LANE) -> int:
    """Largest aligned block <= preferred that divides (padded) dim."""
    if dim <= preferred:
        return round_up(dim, align) if dim % align else dim
    b = preferred
    while b > align and dim % b:
        b -= align
    return max(b, align)
