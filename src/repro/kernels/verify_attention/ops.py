"""Jitted wrapper for the paged flash-verify kernel (model-layout adapter).

Models hand verify attention a (B, T, Hq, D) window query and the shared
(P, page_size, Hkv, D) page pools; the kernel wants the T window rows and
the G grouped queries flattened onto one row axis per KV head,
(B, Hkv, T*G, D) with rows t-major — so the kernel's ``row // G`` recovers
the window offset for causal masking.  The adapter transposes/reshapes
(Hq = Hkv * G is exactly the kv-major head order the models already use)
and jits with a static interpret flag.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import stack_pools
from repro.kernels.verify_attention.verify_attention import (
    paged_flash_verify,
)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention_op(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray,
                              block_tables: jnp.ndarray, pos: jnp.ndarray,
                              k_scales: Optional[jnp.ndarray] = None,
                              v_scales: Optional[jnp.ndarray] = None,
                              layer=0, lengths: Optional[jnp.ndarray] = None,
                              interpret: Optional[bool] = None
                              ) -> jnp.ndarray:
    """q: (B, T, Hq, D); pages (L, P, page_size, Hkv, Dv) read at
    ``layer``, or one layer's (P, page_size, Hkv, Dv); block_tables
    (B, NB); pos (B,) first window position; ``lengths`` (B,) keys each
    row reads (0: a free row), ``None`` for ``pos + T``.  Returns
    (B, T, Hq, Dv).  ``k_scales``/``v_scales`` ((L, P, page_size) or
    (P, page_size) float32) mark int8 pages; dequant fuses into the
    kernel."""
    k_pages, v_pages, k_scales, v_scales = stack_pools(
        k_pages, v_pages, k_scales, v_scales)
    b, t, hq, d = q.shape
    hkv = k_pages.shape[3]
    dv = v_pages.shape[-1]
    g = hq // hkv
    qg = (q.reshape(b, t, hkv, g, d)
          .transpose(0, 2, 1, 3, 4)           # (B, Hkv, T, G, D)
          .reshape(b, hkv, t * g, d))
    o = paged_flash_verify(qg, k_pages, v_pages, block_tables, pos,
                           t_window=t, layer=layer, lengths=lengths,
                           k_scales=k_scales, v_scales=v_scales,
                           interpret=interpret)
    return (o.reshape(b, hkv, t, g, dv)
            .transpose(0, 2, 1, 3, 4)
            .reshape(b, t, hq, dv))
