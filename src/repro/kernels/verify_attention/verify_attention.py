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
with a widened query block: one grid step per batch row, the kernel
copying the row's pages out of the stacked (L, P, page, Hkv, D) pool in
compute blocks of several pages, the next block in flight while the
current one is scored, and no more pages than ``pos + T`` keys need.

  q arrives as (B, Hkv, T*G, D): T window positions x G grouped queries
  per KV head, flattened onto the kernel's row axis.  Row r = t*G + g
  holds the query for window offset t, so causal masking *within* the
  window is a per-row valid limit ``pos + r // G`` — query t sees the
  committed prefix plus window tokens 0..t (each window token's K/V row is
  written before the kernel runs, exactly like single-token decode).

The online-softmax body (running max / running sum / output accumulator in
VMEM scratch, row reductions by a lane-rotation tree) is the decode
kernel's own ``_attend_block`` — T=1 degenerates to decode exactly.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import paged_attend


def paged_flash_verify(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                       pos: jnp.ndarray, *, t_window: int, layer=0,
                       lengths: Optional[jnp.ndarray] = None,
                       scale: Optional[float] = None,
                       k_scales: Optional[jnp.ndarray] = None,
                       v_scales: Optional[jnp.ndarray] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hkv, T*G, D) — T window rows x G grouped queries, row-major;
    k_pages/v_pages: (L, P, page_size, Hkv, Dv) read at ``layer``;
    block_tables: (B, NB) int32; pos: (B,) first window position (cache
    valid through pos-1, window rows written at pos..pos+T-1 before this
    call); ``lengths`` (B,) keys each row reads (0: a free row), ``None``
    for ``pos + T``.

    Returns (B, Hkv, T*G, Dv).  One dispatch scores every window position:
    row t*G+g masks keys past ``pos+t`` (causal within the window); pages
    past ``pos+T-1`` are neither copied nor scored.

    ``k_scales`` / ``v_scales`` ((L, P, page_size) float32, both or
    neither) mark the pages int8-quantized; dequant fuses into the kernel
    exactly as in the paged flash-decode kernel.
    """
    if q.shape[2] % t_window:
        raise ValueError(f"q rows {q.shape[2]} not a multiple of "
                         f"t_window={t_window}")
    return paged_attend(q, k_pages, v_pages, block_tables, pos, layer=layer,
                        lengths=lengths, t_window=t_window, scale=scale,
                        k_scales=k_scales, v_scales=v_scales,
                        interpret=interpret)
