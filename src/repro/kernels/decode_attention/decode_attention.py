"""Flash-decode Pallas TPU kernels: few-token GQA attention over a KV cache.

The serving hot loop's attention is the paper's HW-vs-SW story in miniature.
The SW-path shape (``ref.py`` / the dense jnp fallback) materializes a
(B, H, Smax) score row against the *entire padded cache* and round-trips it
through memory.  These kernels keep the online-softmax running max / running
sum / output accumulator register-resident in VMEM scratch across the KV
blocks — the warp-reduce discipline of ``core.hw_backend`` — and visit only
cache blocks that contain valid positions.

Dense cache (:func:`flash_decode`): grid = (B, kv_blocks), kv innermost
with "arbitrary" semantics.  Per-slot positions arrive as a scalar-prefetch
operand (SMEM), so blocks past ``pos`` are skipped with ``pl.when``.  Each
K/V block carries every KV head: the (…, Hkv, D) cache is viewed as
(…, Hkv*D) so a block is (block_k, Hkv*D) and satisfies the TPU tiling rule
(last two block dims divisible by (8, 128) or equal to the array's); the
body takes head h as the lane-aligned slice [h*D, (h+1)*D).

Paged pool (:func:`paged_attend`): the kernel gathers its own pages.  The
stacked (L, P, page, Hkv, D) pools stay in HBM (``memory_space=pl.ANY``)
and the layer index rides the scalar-prefetch channel with the block
tables, so no per-layer slice or relayout of a pool exists in the step.
grid = (B,): each row walks its live pages in compute blocks of
:func:`pages_per_block` pages, each page one async copy ``pool.at[layer,
page]`` into a double-buffered VMEM block, the next block (or the next
live row's first) in flight while the current one is scored.  A row reads
``ceil(lengths[b] / page)`` pages and no more; a row of length 0 reads and
computes nothing.

Within a block the row reductions (max / sum over the key lane axis) fold
the block to 128 lanes, then run a log2(128)-step rotate-and-combine tree —
lane rotations on the XLU, the register-exchange tree of the paper's HW
path (``hw_backend.warp_reduce`` is its reshape form, which Mosaic cannot
lower).

Layout: q (B, Hkv, R, D) — R = t_window * G query rows per KV head, row
r = t*G + g; dense k/v (B, Smax, Hkv, D); pos (B,) int32 with query row r
seeing keys through ``pos + r // G``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

# fast memory the paged kernel's K/V blocks and their f32 working set may
# take: under the 16 MiB a v5e kernel gets by default, with room for q, o
# and the softmax scratch
_PAGED_VMEM_BUDGET = 12 * 1024 * 1024
# widest compute block, in keys: past this a block buys no fewer grid
# overheads and only widens the score tile
_PAGED_MAX_KEYS = 2048


def _row_reduce(x: jnp.ndarray, width: int, op: str) -> jnp.ndarray:
    """(R, width) -> (R, 1).  A width of 128·2^k folds its 128-lane
    chunks together, then rotates the lane axis by 64, 32, ..., 1 and
    combines — after log2(128) exchanges every lane holds the full
    reduction.  Other power-of-two widths run the tree directly."""
    fn = jnp.maximum if op == "max" else jnp.add
    if width & (width - 1) == 0:
        while width > 128:
            width //= 2
            x = fn(x[:, :width], x[:, width:])
        shift = width // 2
        while shift >= 1:
            x = fn(x, pltpu.roll(x, shift, 1))
            shift //= 2
        return x[:, :1]
    red = jnp.max if op == "max" else jnp.sum
    return red(x, axis=-1, keepdims=True)


def _attend_block(q_ref, k_of, v_of, m_scr, l_scr, acc_scr, *, k0, pos,
                  length, block_k: int, heads: int, t_window: int,
                  scale: float, k_scale=None, v_scale=None):
    """Online-softmax update of every head's (m, l, acc) scratch with one
    block of ``block_k`` keys starting at key ``k0``.

    ``k_of(h)`` / ``v_of(h)`` give head h's (block_k, D) / (block_k, Dv)
    keys and values in their storage dtype; q_ref holds (1, Hkv, R, D).
    Row r sees keys ``<= pos + r // G`` and below ``length``; key rows at
    or past ``min(pos + t_window, length)`` are zeroed before the value
    contraction, so stale or poisoned rows (a tail page, a fresh growth
    page, NaN in interpret mode) cannot leak through ``0 * NaN``.
    ``k_scale`` / ``v_scale`` ((1, block_k) float32) dequantize int8 keys
    and values in score space: ``(q . k_int) * ks`` and ``(p * vs) . v_int``,
    with the scales of keys a row may not see masked out alike.
    """
    rows = q_ref.shape[2]
    group = rows // t_window
    end = jnp.minimum(pos + t_window, length)      # keys any row may see
    row_ids = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
    k_ids = k0 + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
    limit = pos
    if t_window > 1:
        limit = pos + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 0) // group
    live = (k_ids <= limit) & (k_ids < length)
    for h in range(heads):
        q = q_ref[0, h].astype(jnp.float32)           # (R, D)
        k = k_of(h).astype(jnp.float32)               # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if k_scale is not None:
            s = s * k_scale
        s = jnp.where(live, s * scale, DEFAULT_MASK_VALUE)
        v = jnp.where(row_ids < end, v_of(h).astype(jnp.float32), 0.0)
        m_prev = m_scr[h]                             # (R, 1)
        m_new = jnp.maximum(m_prev, _row_reduce(s, block_k, "max"))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # (R, bk)
        l_scr[h] = alpha * l_scr[h] + _row_reduce(p, block_k, "sum")
        if v_scale is not None:
            p = jnp.where(live, p * v_scale, 0.0)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[h] = acc_scr[h] * alpha + pv
        m_scr[h] = m_new


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr):
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _attend_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_k: int, kv_steps: int,
                   heads: int):
    """Dense-cache decode: k_ref/v_ref blocks (1, block_k, Hkv*D), head h
    the lane slice [h*D, (h+1)*D)."""
    b = pl.program_id(0)
    kj = pl.program_id(1)
    pos = pos_ref[b]
    d = q_ref.shape[3]
    dv = acc_scr.shape[-1]

    @pl.when(kj == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    # Skip cache blocks entirely beyond the valid length: the whole point —
    # decode traffic tracks the live sequence, not the padded buffer.
    @pl.when(kj * block_k <= pos)
    def _compute():
        _attend_block(q_ref, lambda h: k_ref[0, :, h * d:(h + 1) * d],
                      lambda h: v_ref[0, :, h * dv:(h + 1) * dv],
                      m_scr, l_scr, acc_scr, k0=kj * block_k, pos=pos,
                      length=pos + 1, block_k=block_k, heads=heads,
                      t_window=1, scale=scale)

    @pl.when(kj == kv_steps - 1)
    def _final():
        _finalize(o_ref, l_scr, acc_scr)


def _flat_heads(x: jnp.ndarray) -> jnp.ndarray:
    """(…, Hkv, D) -> (…, Hkv*D): a row-major view."""
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
# paged variant: the kernel gathers its own pages from the stacked pool
# ---------------------------------------------------------------------------

def pages_per_block(nb: int, page_size: int, rows: int, heads: int,
                    d: int, itemsize: int) -> int:
    """Pages per compute block of the paged kernel, from the shapes it
    sees: ``nb`` table columns, ``rows`` (= t_window * G) query rows per
    KV head, ``heads`` x ``d`` per cache row at ``itemsize`` bytes.

    The largest power of two, at most ``nb``, whose block fits
    ``_PAGED_VMEM_BUDGET`` beside the row's own blocks (q and o
    double-buffered, the f32 softmax scratch at lane width): K and V
    double-buffered at storage width, one head's keys and values at f32,
    and the (rows, keys) scores, probabilities and masks — and spans at
    most ``_PAGED_MAX_KEYS`` keys.  The engine's ``decode_grid_tokens``
    counter reads the grid through this same function."""
    fixed = heads * rows * (2 * 2 * d * 2       # q, o x two buffers
                            + 3 * 128 * 4)      # m, l, acc at f32
    per_key = (4 * heads * d * itemsize         # K, V x two buffers
               + 2 * d * 4                      # one head's K, V at f32
               + 5 * rows * 4)                  # scores, probs, masks
    ppb = 1
    while (2 * ppb <= nb and 2 * ppb * page_size <= _PAGED_MAX_KEYS
           and fixed + 2 * ppb * page_size * per_key
           <= _PAGED_VMEM_BUDGET):
        ppb *= 2
    return ppb


def _paged_kernel(len_ref, nxt_ref, bt_ref, layer_ref, pos_ref, q_ref, *refs,
                  quantized: bool, ppb: int, page: int, nb: int,
                  heads: int, t_window: int, scale: float,
                  heads_major: bool):
    """One grid step per batch row.  ``len_ref[b]`` keys of row b are
    read, ``ppb`` pages a block; ``nxt_ref[0]`` is the first row with
    keys, ``nxt_ref[b + 1]`` the next one after row b (B if none), so the
    last block of a row prefetches the first block of the next live row
    and the pipeline never drains between rows.  ``slot_ref`` (SMEM)
    carries which of the two buffers holds the block in flight across
    grid steps — the axis is sequential ("arbitrary")."""
    if quantized:
        ks_ref, vs_ref, *refs = refs
    (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref, m_scr, l_scr,
     acc_scr) = refs
    streams = ((k_hbm, k_buf), (v_hbm, v_buf))
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    bk = ppb * page
    d = q_ref.shape[3]

    def copy(s, slot, j, pid):
        src, buf = streams[s]
        return pltpu.make_async_copy(src.at[layer, pid], buf.at[slot, j],
                                     sems.at[s, slot])

    def n_pages(row, blk):
        """Pages of block ``blk`` that row ``row`` reads."""
        live = (len_ref[row] + page - 1) // page
        return jnp.minimum(ppb, live - blk * ppb)

    def start(row, blk, slot):
        n = n_pages(row, blk)
        for j in range(ppb):
            @pl.when(j < n)
            def _():
                pid = bt_ref[row * nb + blk * ppb + j]
                for s in range(len(streams)):
                    copy(s, slot, j, pid).start()

    def wait(row, blk, slot):
        n = n_pages(row, blk)
        for j in range(ppb):
            @pl.when(j < n)
            def _():
                for s in range(len(streams)):
                    copy(s, slot, j, 0).wait()

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        first = nxt_ref[0]

        @pl.when(first < n_rows)
        def _():
            start(first, 0, 0)

    length = len_ref[b]

    @pl.when(length == 0)
    def _free():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _row():
        _init_scratch(m_scr, l_scr, acc_scr)
        n_blk = (length + bk - 1) // bk
        pos = pos_ref[b]

        def block(i, carry):
            @pl.when(i < n_blk)
            def _():
                slot = slot_ref[0]
                more = i + 1 < n_blk
                nrow = jnp.where(more, b, nxt_ref[b + 1])
                nblk = jnp.where(more, i + 1, 0)

                @pl.when(nrow < n_rows)
                def _():
                    start(nrow, nblk, 1 - slot)

                wait(b, i, slot)
                kb, vb = k_buf.at[slot], v_buf.at[slot]
                ks = vs = None
                if quantized:
                    keys = pl.ds(pl.multiple_of(i * bk, bk), bk)
                    ks = ks_ref[0, :, keys]
                    vs = vs_ref[0, :, keys]
                _attend_block(
                    q_ref, _head_reader(kb, bk, heads_major),
                    _head_reader(vb, bk, heads_major), m_scr, l_scr,
                    acc_scr, k0=i * bk, pos=pos, length=length,
                    block_k=bk, heads=heads, t_window=t_window,
                    scale=scale, k_scale=ks, v_scale=vs)
                slot_ref[0] = 1 - slot
            return carry

        jax.lax.fori_loop(0, pl.cdiv(nb, ppb), block, 0)
        _finalize(o_ref, l_scr, acc_scr)


def _head_reader(buf, bk: int, heads_major: bool):
    """``h -> (bk, D)``: head h's rows of a (ppb, page, Hkv, D) block, or
    of a (ppb, Hkv, page, D) block when ``heads_major``.

    bfloat16 pages pack heads 2i and 2i+1 into one 32-bit word, low half
    first; the block is read as 32-bit words and head h's half becomes
    its float32 value by a shift or a mask, with no relayout of the
    packed rows."""
    d = buf.shape[-1]
    if heads_major:
        return lambda h: buf[:, h].astype(jnp.float32).reshape(bk, d)
    if buf.dtype != jnp.bfloat16 or buf.shape[2] % 2:
        return lambda h: buf[:, :, h, :].reshape(bk, d)
    words = buf.bitcast(jnp.uint32)

    def read(h):
        w = words[:, :, h // 2, :].reshape(bk, d)
        w = w << 16 if h % 2 == 0 else w & jnp.uint32(0xFFFF0000)
        return pltpu.bitcast(w, jnp.float32)
    return read


def paged_attend(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 block_tables: jnp.ndarray, pos: jnp.ndarray, *,
                 layer, lengths: Optional[jnp.ndarray], t_window: int,
                 scale: Optional[float],
                 k_scales: Optional[jnp.ndarray],
                 v_scales: Optional[jnp.ndarray],
                 interpret: Optional[bool]) -> jnp.ndarray:
    """pallas_call over the stacked paged pool for ``t_window`` query
    positions starting at ``pos`` (decode: 1; speculative verify: k).

    q: (B, Hkv, t_window*G, D); pools (L, P, page_size, Hkv, Dv), read at
    ``layer`` (a scalar, traced or static) where they live in HBM;
    block_tables (B, NB); ``lengths`` (B,) int32: the keys row b reads,
    page by page — 0 marks a free row, which reads and computes nothing
    and returns zeros.  ``None`` reads ``min(pos + t_window, NB *
    page_size)`` keys of every row.  Scales (L, P, page_size) float32
    mark int8 pools: each row's scales at ``layer`` are gathered through
    its table (NB x page_size floats a row, 1/Hkv/D of its pages' bytes)
    and reach the kernel as a lane vector per row, since a page's scale
    row is narrower than the 128-lane tile a copy may slice when
    page_size < 128."""
    from repro.kernels.common import default_interpret

    if interpret is None:
        interpret = default_interpret()
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    quantized = k_scales is not None
    b, hkv, rows, d = q.shape
    _, _, page_size, _, dv = v_pages.shape
    nb = block_tables.shape[1]
    block_tables = block_tables.astype(jnp.int32)
    if scale is None:
        scale = d ** -0.5
    pos = pos.astype(jnp.int32)
    if lengths is None:
        lengths = pos + t_window
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, nb * page_size)
    ppb = pages_per_block(nb, page_size, rows, hkv, max(d, dv),
                          k_pages.dtype.itemsize)
    # nxt[0]: first row with keys; nxt[r + 1]: next such row after r
    has = jnp.where(lengths > 0, jnp.arange(b, dtype=jnp.int32), b)
    after = jax.lax.cummin(has, reverse=True)
    nxt = jnp.concatenate([after, jnp.full((1,), b, jnp.int32)])
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    # a pool whose Hkv rows fill less than a 32-bit sublane word (int8 at
    # Hkv < 4, bf16 at Hkv 1) is laid out by XLA with the page axis
    # second-minor: read it in that order, where the swap is free
    heads_major = hkv * k_pages.dtype.itemsize < 4
    if heads_major:
        k_pages = jnp.swapaxes(k_pages, 2, 3)
        v_pages = jnp.swapaxes(v_pages, 2, 3)
    kernel = functools.partial(
        _paged_kernel, quantized=quantized, ppb=ppb, page=page_size, nb=nb,
        heads=hkv, t_window=t_window, scale=scale, heads_major=heads_major)
    k_buf = pltpu.VMEM((2, ppb) + k_pages.shape[2:], k_pages.dtype)
    v_buf = pltpu.VMEM((2, ppb) + v_pages.shape[2:], v_pages.dtype)
    row_map = lambda bi, *_: (bi, 0, 0, 0)
    in_specs = [pl.BlockSpec((1, hkv, rows, d), row_map)]
    operands = [q]
    if quantized:
        # each row's scales, gathered through its table at this layer and
        # laid along lanes: (B, 1, n_blk * bk), zero past the table
        keys = pl.cdiv(nb, ppb) * ppb
        bt = jnp.pad(block_tables, ((0, 0), (0, keys - nb)))
        for sc in (k_scales, v_scales):
            rows_sc = jnp.where((jnp.arange(keys) < nb)[:, None],
                                sc[layer[0], bt], 0.0)
            operands.append(rows_sc.reshape(b, 1, keys * page_size))
            in_specs.append(pl.BlockSpec((1, 1, keys * page_size),
                                         lambda bi, *_: (bi, 0, 0)))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs += [any_spec, any_spec]
    operands += [k_pages, v_pages]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, rows, dv), row_map),
        scratch_shapes=[k_buf, v_buf, pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)]
        + _scratch(hkv, rows, dv),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(lengths, nxt, block_tables.reshape(-1), layer, pos, *operands)


def stack_pools(k_pages, v_pages, k_scales=None, v_scales=None):
    """One layer's (P, page, Hkv, D) pools (and (P, page) scales) as a
    stack of one, so single-layer callers read ``layer`` 0; stacked
    (L, P, page, Hkv, D) pools pass through."""
    if k_pages.ndim == 5:
        return k_pages, v_pages, k_scales, v_scales
    one = lambda x: None if x is None else x[None]
    return one(k_pages), one(v_pages), one(k_scales), one(v_scales)


def paged_flash_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                       pos: jnp.ndarray, *, layer=0,
                       lengths: Optional[jnp.ndarray] = None,
                       scale: Optional[float] = None,
                       k_scales: Optional[jnp.ndarray] = None,
                       v_scales: Optional[jnp.ndarray] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hkv, G, D); k_pages/v_pages: (L, P, page_size, Hkv, Dv) read
    at ``layer``; block_tables: (B, NB) int32 physical page per logical
    block; pos: (B,) int32 with positions <= pos[b] valid; ``lengths``
    as in :func:`paged_attend`.  Returns (B, Hkv, G, Dv).

    ``k_scales`` / ``v_scales`` ((L, P, page_size) float32, both or
    neither) mark the pages int8-quantized: each page streams at 1
    byte/element and dequant happens inside the kernel — the
    capacity-for-bandwidth trade measured by the roofline replay.
    """
    return paged_attend(q, k_pages, v_pages, block_tables, pos, layer=layer,
                        lengths=lengths, t_window=1, scale=scale,
                        k_scales=k_scales, v_scales=v_scales,
                        interpret=interpret)
