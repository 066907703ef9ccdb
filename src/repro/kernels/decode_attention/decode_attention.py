"""Flash-decode Pallas TPU kernel: single-token GQA attention over a KV cache.

The serving hot loop's attention is the paper's HW-vs-SW story in miniature.
The SW-path shape (``ref.py`` / the dense jnp fallback) materializes a
(B, H, Smax) score row against the *entire padded cache* and round-trips it
through memory.  This kernel keeps the online-softmax running max / running
sum / output accumulator register-resident in VMEM scratch across the KV
grid axis — the warp-reduce discipline of ``core.hw_backend`` — and visits
only cache blocks that contain valid positions:

  grid = (B, kv_blocks), kv innermost with "arbitrary" semantics.
  Per-slot sequence lengths arrive as a scalar-prefetch operand (SMEM), so
  blocks past ``pos`` are skipped with ``pl.when`` — decode work scales with
  the *valid* length, not ``max_seq``.

Each K/V block carries every KV head: the (…, Hkv, D) cache is viewed as
(…, Hkv*D) — a free row-major reshape — so a block is (block_k, Hkv*D) and
satisfies the TPU tiling rule (last two block dims divisible by (8, 128) or
equal to the array's), which a one-head (block_k, 1, D) block cannot when
Hkv > 1.  The body loops over heads, taking head h as the lane-aligned
slice [h*D, (h+1)*D).

Within a block the row reductions (max / sum over the block_k lane axis)
are a log2(block_k)-step rotate-and-combine tree when block_k is a power
of two — lane rotations on the XLU, the register-exchange tree of the
paper's HW path (``hw_backend.warp_reduce`` is its reshape form, which
Mosaic cannot lower).

Layout: q (B, Hkv, G, D) — grouped queries per KV head; k/v (B, Smax, Hkv,
D); pos (B,) int32 with the cache valid through index ``pos`` inclusive.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _row_reduce(x: jnp.ndarray, width: int, op: str) -> jnp.ndarray:
    """(R, width) -> (R, 1).  For width 2^n: rotate the lane axis by
    width/2, width/4, ..., 1 and combine — after log2(width) exchanges
    every lane holds the full reduction."""
    fn = jnp.maximum if op == "max" else jnp.add
    if width & (width - 1) == 0:
        shift = width // 2
        while shift >= 1:
            x = fn(x, pltpu.roll(x, shift, 1))
            shift //= 2
        return x[:, :1]
    red = jnp.max if op == "max" else jnp.sum
    return red(x, axis=-1, keepdims=True)


def _attend_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_k: int, kv_steps: int,
                   heads: int, t_window: int = 1, ks_ref=None, vs_ref=None):
    """Online-softmax body shared by decode (t_window=1) and the
    speculative verify kernel.  q_ref block (1, Hkv, R, D) with R =
    t_window*G query rows per KV head, row r = t*G + g; k_ref/v_ref blocks
    (1, block_k, Hkv*D) / (1, block_k, Hkv*Dv).  Row r attends positions
    <= pos + r // G: the committed prefix plus window tokens 0..t."""
    b = pl.program_id(0)
    kj = pl.program_id(1)
    pos = pos_ref[b]                       # first (or only) query position
    last = pos + t_window - 1              # most permissive row limit
    rows, d = q_ref.shape[2], q_ref.shape[3]
    dv = acc_scr.shape[-1]
    group = rows // t_window

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Skip cache blocks entirely beyond the valid length: the whole point —
    # decode traffic tracks the live sequence, not the padded buffer.
    @pl.when(kj * block_k <= last)
    def _compute():
        k_all = k_ref[0].astype(jnp.float32)          # (bk, Hkv*D)
        v_all = v_ref[0].astype(jnp.float32)          # (bk, Hkv*Dv)
        if ks_ref is not None:
            # int8 pages: dequant fused into the gather — the block was
            # streamed at 1 byte/elem, the scale rides its own (bk, 1)
            # per-row block through the same page index map
            k_all = k_all * ks_ref[0]
            v_all = v_all * vs_ref[0]
        # zero rows past the last query: a partial tail block or a fresh
        # growth page reads garbage (NaN in interpret mode) and 0 * NaN
        # would poison the contraction
        row_ids = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)
        v_all = jnp.where(row_ids <= last, v_all, 0.0)
        k_ids = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        limit = pos
        if t_window > 1:
            limit = pos + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 0) // group
        live = k_ids <= limit
        for h in range(heads):
            q = q_ref[0, h].astype(jnp.float32)       # (R, D)
            k = k_all[:, h * d:(h + 1) * d]           # (bk, D)
            v = v_all[:, h * dv:(h + 1) * dv]         # (bk, Dv)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s * scale, DEFAULT_MASK_VALUE)
            m_prev = m_scr[h]                         # (R, 1)
            m_new = jnp.maximum(m_prev, _row_reduce(s, block_k, "max"))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                    # (R, bk)
            l_scr[h] = alpha * l_scr[h] + _row_reduce(p, block_k, "sum")
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_scr[h] = acc_scr[h] * alpha + pv
            m_scr[h] = m_new

    @pl.when(kj == kv_steps - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _flat_heads(x: jnp.ndarray) -> jnp.ndarray:
    """(…, Hkv, D) -> (…, Hkv*D): a free row-major view."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def _scratch(heads: int, rows: int, dv: int):
    return [pltpu.VMEM((heads, rows, 1), jnp.float32),
            pltpu.VMEM((heads, rows, 1), jnp.float32),
            pltpu.VMEM((heads, rows, dv), jnp.float32)]


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 pos: jnp.ndarray, *, scale: Optional[float] = None,
                 block_k: int = 256,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hkv, G, D); k/v: (B, Smax, Hkv, Dv); pos: (B,) int32.

    Returns (B, Hkv, G, Dv).  Positions > pos[b] are masked; blocks whose
    first index exceeds pos[b] are skipped (no memory traffic, no compute).
    """
    from repro.kernels.common import default_interpret

    if interpret is None:
        interpret = default_interpret()
    b, hkv, g, d = q.shape
    smax = k.shape[1]
    dv = v.shape[-1]
    if scale is None:
        scale = d ** -0.5
    block_k = min(block_k, smax)
    kv_steps = pl.cdiv(smax, block_k)

    kernel = functools.partial(_attend_kernel, scale=scale, block_k=block_k,
                               kv_steps=kv_steps, heads=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv_steps),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda bi, j, pos_ref: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, hkv * d),
                         lambda bi, j, pos_ref: (bi, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, hkv * dv),
                         lambda bi, j, pos_ref: (bi, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, dv),
                               lambda bi, j, pos_ref: (bi, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=_scratch(hkv, g, dv),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pos.astype(jnp.int32), q, _flat_heads(k), _flat_heads(v))


# ---------------------------------------------------------------------------
# paged variant: KV lives in a shared block pool, gathered via block tables
# ---------------------------------------------------------------------------

def paged_attend_kernel(pos_ref, bt_ref, q_ref, *refs, quantized: bool,
                        **kw):
    """The shared online-softmax body behind a block-table gather — the
    *only* paged difference is where the KV block came from (the index
    maps walk the scalar-prefetched block table), which is exactly the
    paper's HW-contiguous vs SW-indirection split.  Quantized pools
    interleave a per-row scale block behind each value block (k, k_scales,
    v, v_scales); the dequant multiply fuses into the same body."""
    del bt_ref  # consumed by the index maps, not the body
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    _attend_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, ks_ref=ks_ref, vs_ref=vs_ref, **kw)


def paged_attend(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 block_tables: jnp.ndarray, pos: jnp.ndarray, *,
                 t_window: int, scale: Optional[float],
                 k_scales: Optional[jnp.ndarray],
                 v_scales: Optional[jnp.ndarray],
                 interpret: Optional[bool]) -> jnp.ndarray:
    """pallas_call over the paged pool for ``t_window`` query positions
    starting at ``pos`` (decode: 1; speculative verify: k).  q: (B, Hkv,
    t_window*G, D); pages (P, page_size, Hkv, Dv).  Blocks past the last
    query clamp their index to the last live block — the Pallas pipeline
    only streams a block when its index *changes*, so dead blocks cost no
    memory traffic (and ``pl.when`` skips their compute); dead slots'
    runaway ``pos`` also clamps to the final table column."""
    from repro.kernels.common import default_interpret

    if interpret is None:
        interpret = default_interpret()
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    quantized = k_scales is not None
    b, hkv, rows, d = q.shape
    page_size = k_pages.shape[1]
    dv = v_pages.shape[-1]
    nb = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5

    kernel = functools.partial(paged_attend_kernel, quantized=quantized,
                               scale=scale, block_k=page_size, kv_steps=nb,
                               heads=hkv, t_window=t_window)

    def kv_map(bi, j, pos_ref, bt_ref):
        jc = jnp.minimum(jnp.minimum(
            j, (pos_ref[bi] + t_window - 1) // page_size), nb - 1)
        return (bt_ref[bi, jc], 0, 0)

    q_spec = pl.BlockSpec((1, hkv, rows, d),
                          lambda bi, j, pos_ref, bt_ref: (bi, 0, 0, 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, page_size, hkv * d), kv_map,
                          memory_space=pltpu.VMEM)
    v_spec = pl.BlockSpec((1, page_size, hkv * dv), kv_map,
                          memory_space=pltpu.VMEM)
    s_spec = pl.BlockSpec((1, page_size, 1), kv_map,
                          memory_space=pltpu.VMEM)
    kp, vp = _flat_heads(k_pages), _flat_heads(v_pages)
    if quantized:
        in_specs = [q_spec, k_spec, s_spec, v_spec, s_spec]
        operands = (q, kp, k_scales[..., None], vp, v_scales[..., None])
    else:
        in_specs = [q_spec, k_spec, v_spec]
        operands = (q, kp, vp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, rows, dv),
                               lambda bi, j, pos_ref, bt_ref: (bi, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=_scratch(hkv, rows, dv),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pos.astype(jnp.int32), block_tables.astype(jnp.int32), *operands)


def paged_flash_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                       pos: jnp.ndarray, *, scale: Optional[float] = None,
                       k_scales: Optional[jnp.ndarray] = None,
                       v_scales: Optional[jnp.ndarray] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hkv, G, D); k_pages/v_pages: (P, page_size, Hkv, Dv);
    block_tables: (B, NB) int32 physical page per logical block; pos: (B,)
    int32 with positions <= pos[b] valid.  Returns (B, Hkv, G, Dv).

    The kv grid axis walks *logical* blocks; each step's page is fetched
    through ``block_tables`` inside the BlockSpec index map, with the
    block-table row arriving as a scalar-prefetch operand (SMEM) so the
    gather address is known before the DMA issues.

    ``k_scales`` / ``v_scales`` ((P, page_size) float32, both or neither)
    mark the pages int8-quantized: each value block streams at 1
    byte/element and its per-row scale block follows the same page index
    map, so dequant happens after the gather, inside the kernel — the
    capacity-for-bandwidth trade measured by the roofline replay.
    """
    return paged_attend(q, k_pages, v_pages, block_tables, pos, t_window=1,
                        scale=scale, k_scales=k_scales, v_scales=v_scales,
                        interpret=interpret)
