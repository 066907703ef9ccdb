"""Jitted wrapper for the flash-decode kernel (model-layout adapter).

Models hand attention a (B, 1, Hq, D) single-token query and (B, Smax, Hkv,
D) caches; the kernel wants grouped queries (B, Hkv, G, D).  The adapter
reshapes (zero-copy: Hq = Hkv * G is exactly the kv-major head order the
models already use) and jits with static block/interpret flags.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    flash_decode,
    paged_flash_decode,
    stack_pools,
)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_op(q: jnp.ndarray, k_cache: jnp.ndarray,
                        v_cache: jnp.ndarray, pos: jnp.ndarray,
                        block_k: int = 256,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, 1, Hq, D); caches (B, Smax, Hkv, Dv); pos (B,).

    Returns (B, 1, Hq, Dv)."""
    b, _, hq, d = q.shape
    hkv = k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    o = flash_decode(qg, k_cache, v_cache, pos, block_k=block_k,
                     interpret=interpret)
    return o.reshape(b, 1, hq, dv)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_op(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray,
                              block_tables: jnp.ndarray, pos: jnp.ndarray,
                              k_scales: Optional[jnp.ndarray] = None,
                              v_scales: Optional[jnp.ndarray] = None,
                              layer=0, lengths: Optional[jnp.ndarray] = None,
                              interpret: Optional[bool] = None
                              ) -> jnp.ndarray:
    """q: (B, 1, Hq, D); pages (L, P, page_size, Hkv, Dv) read at
    ``layer``, or one layer's (P, page_size, Hkv, Dv); block_tables
    (B, NB) physical page per logical block; pos (B,); ``lengths`` (B,)
    keys each row reads (0: a free row), ``None`` for ``pos + 1``.

    Returns (B, 1, Hq, Dv).  The kernel copies each row's pages out of
    the pool itself, several pages a compute block.
    ``k_scales``/``v_scales`` ((L, P, page_size) or (P, page_size)
    float32) mark int8 pages; dequant fuses into the kernel."""
    k_pages, v_pages, k_scales, v_scales = stack_pools(
        k_pages, v_pages, k_scales, v_scales)
    b, _, hq, d = q.shape
    hkv = k_pages.shape[3]
    dv = v_pages.shape[-1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    o = paged_flash_decode(qg, k_pages, v_pages, block_tables, pos,
                           layer=layer, lengths=lengths, k_scales=k_scales,
                           v_scales=v_scales, interpret=interpret)
    return o.reshape(b, 1, hq, dv)
