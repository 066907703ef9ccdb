"""Paged flash-verify Pallas kernel: k-token speculative verify in one dispatch.

Speculative decoding is the serving-side version of the paper's HW-vs-SW
trade-off.  The SW path verifies a k-token draft window with a chunked jnp
loop — k single-token score/softmax round trips through memory (see
``repro.models.attention.paged_verify_attention(backend='jnp')``).  This
kernel is the fused HW path: all k window positions are scored against the
paged KV cache in ONE dispatch, so the per-dispatch overhead that
dominates small-model decode is paid once per window instead of once per
token — the k-for-1 amortization the spec-decode subsystem exists to buy.

Structure is the paged flash-decode kernel (``kernels/decode_attention``)
with a widened query block:

  grid = (B, logical_blocks), kv innermost with "arbitrary" semantics;
  each K/V block carries every KV head (see ``kernels/decode_attention``).
  The block table rides the scalar-prefetch channel (SMEM), so each
  logical block's physical page is resolved before its DMA issues;
  blocks past the window's last position clamp their index — the Pallas
  pipeline only streams a block when its index *changes*, so dead blocks
  cost no fetch.

  q arrives as (B, Hkv, T*G, D): T window positions x G grouped queries
  per KV head, flattened onto the kernel's row axis.  Row r = t*G + g
  holds the query for window offset t, so causal masking *within* the
  window is a per-row valid limit ``pos + r // G`` — query t sees the
  committed prefix plus window tokens 0..t (each window token's K/V row is
  written before the kernel runs, exactly like single-token decode).

The online-softmax body (running max / running sum / output accumulator in
VMEM scratch, row reductions by a lane-rotation tree) is the decode
kernel's own ``_attend_kernel`` — T=1 degenerates to decode exactly.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import paged_attend


def paged_flash_verify(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                       pos: jnp.ndarray, *, t_window: int,
                       scale: Optional[float] = None,
                       k_scales: Optional[jnp.ndarray] = None,
                       v_scales: Optional[jnp.ndarray] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hkv, T*G, D) — T window rows x G grouped queries, row-major;
    k_pages/v_pages: (P, page_size, Hkv, Dv); block_tables: (B, NB) int32;
    pos: (B,) first window position (cache valid through pos-1, window
    rows written at pos..pos+T-1 before this call).

    Returns (B, Hkv, T*G, Dv).  One dispatch scores every window position:
    row t*G+g masks keys past ``pos+t`` (causal within the window), blocks
    past ``pos+T-1`` are neither fetched (index-map clamp) nor computed
    (``pl.when``).

    ``k_scales`` / ``v_scales`` ((P, page_size) float32, both or neither)
    mark the pages int8-quantized: per-row scale blocks ride the same
    page index map and dequant fuses into the gather, exactly as in the
    paged flash-decode kernel.
    """
    if q.shape[2] % t_window:
        raise ValueError(f"q rows {q.shape[2]} not a multiple of "
                         f"t_window={t_window}")
    return paged_attend(q, k_pages, v_pages, block_tables, pos,
                        t_window=t_window, scale=scale, k_scales=k_scales,
                        v_scales=v_scales, interpret=interpret)
