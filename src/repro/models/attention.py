"""Attention: GQA (with optional chunked-flash lowering) and MLA.

The flash-style chunked form is the HW-path story at the XLA level: the
online-softmax running max/sum are register-resident lane reductions (the
warp-reduce pattern), and chunking bounds the score tile exactly like the
Pallas kernel's BlockSpec does.  ``repro.kernels.flash_attention`` is the
explicit-kernel version (forward + backward, causal block-skip), and
:func:`gqa_attention` dispatches to it via ``backend='kernel'`` — the
default on TPU — so training and prefill ride the fused kernel end to
end; the chunked jnp lowering stays as the SW baseline and CPU fallback
(safe to pjit/shard, compiles anywhere).  Decode has the same split via
:func:`decode_attention` / ``repro.kernels.decode_attention``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, rope_freqs

NEG_INF = -1e30


def _scores_mask(sq: int, skv: int, q_offset, causal: bool):
    if not causal:
        return None
    qi = q_offset + jnp.arange(sq)[:, None]
    ki = jnp.arange(skv)[None, :]
    return qi >= ki


def default_attention_backend() -> str:
    """'kernel' (flash Pallas fwd+bwd) on TPU, 'jnp' elsewhere —
    interpret-mode Pallas is correct but not performance-representative."""
    return "kernel" if jax.default_backend() == "tpu" else "jnp"


def _flash_ok(q, k, causal: bool, q_offset: int) -> bool:
    """Can the flash kernel express this call?  q_offset must be zero (the
    kernel's causal mask is anchored at position 0) and causal attention
    must be square; single-token queries stay on the decode/jnp paths."""
    sq, skv = q.shape[1], k.shape[1]
    if q_offset != 0 or sq <= 1:
        return False
    if causal and sq != skv:
        return False
    return True


def gqa_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, q_offset: int = 0,
                  kv_valid_len: Optional[jnp.ndarray] = None,
                  chunk_q: Optional[int] = None,
                  pv_bf16: bool = False,
                  backend: Optional[str] = None) -> jnp.ndarray:
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D), Hq % Hkv == 0.

    backend: 'kernel' (flash-attention Pallas, differentiable, causal
    block-skip) | 'jnp' (chunked softmax — the SW baseline and CPU
    fallback) | None (auto: kernel on TPU, jnp elsewhere).  The kernel
    path ignores chunk_q/pv_bf16 (its score tile is already VMEM-bounded
    and fp32-accumulated) and falls back to jnp for shapes it cannot
    express (q_offset != 0, non-square causal, single-token queries).
    chunk_q: when set and Sq > chunk_q, scan over query chunks with online
    softmax — activation memory O(chunk_q * Skv) instead of O(Sq * Skv).
    pv_bf16: compute the probability x value contraction in bf16 (softmax
    max/sum stay fp32) — halves the dominant score-tensor traffic.
    """
    if backend is None:
        backend = default_attention_backend()
    if backend == "kernel" and _flash_ok(q, k, causal, q_offset):
        from repro.kernels.flash_attention.ops import flash_mha

        return flash_mha(q, k, v, kv_valid_len=kv_valid_len, causal=causal)
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]  # MLA: value head dim may differ from qk head dim
    g = hq // hkv
    scale = d ** -0.5
    qg = q.reshape(b, sq, hkv, g, d)

    def full_attn(qc, q_off):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qc.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        sq_c = qc.shape[1]
        if causal:
            qi = q_off + jnp.arange(sq_c)[:, None]
            ki = jnp.arange(skv)[None, :]
            s = jnp.where(qi >= ki, s, NEG_INF)
        if kv_valid_len is not None:
            ki = jnp.arange(skv)
            valid = ki[None, :] < kv_valid_len[:, None]  # (B, Skv)
            s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        if pv_bf16:
            o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(jnp.bfloat16),
                           v.astype(jnp.bfloat16)).astype(jnp.float32)
        else:
            o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
        return o.reshape(b, sq_c, hq, dv).astype(q.dtype)

    if chunk_q is None or sq <= chunk_q or sq % chunk_q != 0:
        return full_attn(qg, q_offset)

    n_chunks = sq // chunk_q
    qs = qg.reshape(b, n_chunks, chunk_q, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)

    def body(carry, inp):
        idx, qc = inp
        o = full_attn(qc, q_offset + idx * chunk_q)
        return carry, o

    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), qs))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, dv)


def default_decode_backend() -> str:
    """'kernel' (fused flash-decode Pallas) on TPU, 'jnp' elsewhere —
    interpret-mode Pallas is correct but not performance-representative."""
    return "kernel" if jax.default_backend() == "tpu" else "jnp"


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, pos: jnp.ndarray, *,
                     attend_len: Optional[int] = None,
                     backend: Optional[str] = None) -> jnp.ndarray:
    """One-token decode: q (B, 1, Hq, D), caches (B, Smax, Hkv, D),
    pos (B,) current position (cache filled up to and including pos).

    attend_len: static upper bound on the valid cache length (engine-side
    bucketing: max(pos) < attend_len).  The dense-masked SW path scores the
    *entire* padded cache; bounding the read to the live prefix is the
    decode-side version of the paper's HW-path discipline — work scales
    with the sequence actually present, not with ``max_seq``.
    backend: 'kernel' (flash-decode Pallas) | 'jnp' | None (auto).
    """
    b, _, hq, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    if attend_len is not None and attend_len < smax:
        k_cache = k_cache[:, :attend_len]
        v_cache = v_cache[:, :attend_len]
        smax = attend_len
    if backend is None:
        backend = default_decode_backend()
    if backend == "kernel":
        from repro.kernels.decode_attention.ops import decode_attention_op

        return decode_attention_op(q, k_cache, v_cache, pos)
    g = hq // hkv
    scale = d ** -0.5
    qg = q.reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    ki = jnp.arange(smax)
    s = jnp.where((ki[None, :] <= pos[:, None])[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, hq, d).astype(q.dtype)


def _paged_rows(block_tables, pos, t_window: int, page_size: int):
    """Keys each row of a paged read needs: ``pos + t_window``, within
    the table; 0 for a free row — one whose table starts at the trash
    page (the allocator points every released slot's whole row there)."""
    from repro.serve.kv_cache import TRASH_PAGE

    need = jnp.minimum(pos + t_window, block_tables.shape[1] * page_size)
    return jnp.where(block_tables[:, 0] == TRASH_PAGE, 0, need)


def _gather_pages(k_pages, v_pages, block_tables, k_scales, v_scales):
    """The SW indirection: ``jnp.take`` every table entry's page into a
    dense (B, NB*page_size, H, D) view, dequantizing int8 pages with their
    per-row scales taken through the same table."""
    b, nb = block_tables.shape
    page_size, hkv, d = k_pages.shape[1:]
    dv = v_pages.shape[-1]
    k = jnp.take(k_pages, block_tables.reshape(-1), axis=0)
    v = jnp.take(v_pages, block_tables.reshape(-1), axis=0)
    if k_scales is not None:
        ks = jnp.take(k_scales, block_tables.reshape(-1), axis=0)
        vs = jnp.take(v_scales, block_tables.reshape(-1), axis=0)
        k = k.astype(jnp.float32) * ks[..., None, None]
        v = v.astype(jnp.float32) * vs[..., None, None]
    return (k.reshape(b, nb * page_size, hkv, d),
            v.reshape(b, nb * page_size, hkv, dv))


def _paged_args(k_pages, v_pages, block_tables, k_scales, v_scales,
                attend_len, layer):
    """Checks shared by the paged reads; returns the page size and the
    table cut to the ``attend_len`` bucket."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    if (layer is None) != (k_pages.ndim == 4):
        raise ValueError("stacked (L, P, page, H, D) pools take a layer; "
                         "one layer's (P, page, H, D) pools take none")
    page_size = k_pages.shape[-3]
    if attend_len is not None:
        nb = min(block_tables.shape[1], -(-attend_len // page_size))
        block_tables = block_tables[:, :nb]
    return page_size, block_tables


def _one_layer(layer, *pools):
    """``layer``'s slice of each stacked pool (the jnp path's read)."""
    return [p if p is None or layer is None else p[layer] for p in pools]


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray,
                           block_tables: jnp.ndarray, pos: jnp.ndarray, *,
                           layer=None,
                           attend_len: Optional[int] = None,
                           k_scales: Optional[jnp.ndarray] = None,
                           v_scales: Optional[jnp.ndarray] = None,
                           backend: Optional[str] = None) -> jnp.ndarray:
    """One-token decode against a *paged* cache: q (B, 1, Hq, D), page
    pools (L, P, page_size, Hkv, D) read at ``layer`` (or one layer's
    (P, page_size, Hkv, D) with ``layer`` None), block_tables (B, NB)
    mapping logical block j -> physical page, pos (B,) (positions <= pos
    valid).

    This is the layout half of the paper's HW-vs-SW axis: the dense
    :func:`decode_attention` reads a contiguous prefix (the HW path —
    addresses are affine in position), while the paged read must resolve
    every block through the table.  Two lowerings:

      'kernel'  paged flash-decode Pallas kernel — the table rides the
                scalar-prefetch channel and the kernel copies each live
                page out of the pool where it lives, several pages a
                compute block; a row whose table starts at the trash page
                (a free slot) reads and computes nothing, and its output
                is zeros;
      'jnp'     ``jnp.take`` block gather into a dense view, then the
                dense SW softmax — the CPU fallback *and* the
                paper-analogue SW emulation cost (the gather round-trips
                the gathered pages through memory).

    attend_len: static bound on the valid prefix; only the first
    ceil(attend_len / page_size) table columns are visited.

    k_scales/v_scales ((L, P, page_size) or (P, page_size) float32, both
    or neither): the pages are int8-quantized with per-row symmetric
    scales.  Both lowerings dequantize inside the gather — the kernel
    scales scores and probabilities by the scales of the keys it reads;
    the jnp path ``jnp.take``s the scales with the same truncated table
    and broadcasts them over the gathered rows — so the kernel-vs-SW
    parity axis extends unchanged to the quantized tier.
    """
    page_size, block_tables = _paged_args(k_pages, v_pages, block_tables,
                                          k_scales, v_scales, attend_len,
                                          layer)
    if backend is None:
        backend = default_decode_backend()
    if backend == "kernel":
        from repro.kernels.decode_attention.ops import (
            paged_decode_attention_op,
        )

        return paged_decode_attention_op(
            q, k_pages, v_pages, block_tables, pos, k_scales=k_scales,
            v_scales=v_scales, layer=0 if layer is None else layer,
            lengths=_paged_rows(block_tables, pos, 1, page_size))
    k, v = _gather_pages(*_one_layer(layer, k_pages, v_pages), block_tables,
                         *_one_layer(layer, k_scales, v_scales))
    return decode_attention(q, k, v, pos, backend="jnp")


def paged_verify_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray,
                           block_tables: jnp.ndarray, pos: jnp.ndarray, *,
                           layer=None,
                           attend_len: Optional[int] = None,
                           k_scales: Optional[jnp.ndarray] = None,
                           v_scales: Optional[jnp.ndarray] = None,
                           backend: Optional[str] = None) -> jnp.ndarray:
    """k-token speculative verify against the paged cache: q (B, T, Hq, D)
    is the draft window's queries at absolute positions pos..pos+T-1 (whose
    K/V rows are already written through the block tables), page pools
    (L, P, page_size, Hkv, Dv) read at ``layer`` (or one layer's pools with
    ``layer`` None), block_tables (B, NB), pos (B,) first window position.
    Returns (B, T, Hq, Dv).

    Causal masking *within the window* is per-row: query t attends cache
    positions <= pos+t.  T=1 is exactly single-token paged decode.  Two
    lowerings — the spec-decode subsystem's HW-vs-SW axis:

      'kernel'  fused flash-verify Pallas kernel
                (``repro.kernels.verify_attention``): ONE dispatch scores
                all T positions, block table on the scalar-prefetch
                channel, pages copied out of the pool by the kernel,
                online softmax in VMEM scratch — the k-for-1 dispatch
                amortization; free rows as in
                :func:`paged_decode_attention`;
      'jnp'     ``jnp.take`` block gather into a dense view + per-row
                dense-masked softmax over the window — the chunked SW
                verification baseline (and CPU fallback).  Structurally
                the window-batched form of the single-token SW path, so
                greedy outputs stay bit-identical to non-speculative
                decode.

    attend_len: static bound on ``pos + T`` (engine-side bucketing); only
    the first ceil(attend_len / page_size) table columns are visited.

    k_scales/v_scales (float32, both or neither): int8 pages with per-row
    symmetric scales, dequantized inside the gather on both lowerings (see
    :func:`paged_decode_attention`).
    """
    page_size, block_tables = _paged_args(k_pages, v_pages, block_tables,
                                          k_scales, v_scales, attend_len,
                                          layer)
    if backend is None:
        backend = default_decode_backend()
    b, t, hq, d = q.shape
    if backend == "kernel":
        from repro.kernels.verify_attention.ops import (
            paged_verify_attention_op,
        )

        return paged_verify_attention_op(
            q, k_pages, v_pages, block_tables, pos, k_scales=k_scales,
            v_scales=v_scales, layer=0 if layer is None else layer,
            lengths=_paged_rows(block_tables, pos, t, page_size))
    k, v = _gather_pages(*_one_layer(layer, k_pages, v_pages), block_tables,
                         *_one_layer(layer, k_scales, v_scales))
    hkv, dv = k.shape[2], v.shape[-1]
    g = hq // hkv
    qg = q.reshape(b, t, hkv, g, d)
    s = jnp.einsum("bthgd,bkhd->bhtgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    ki = jnp.arange(k.shape[1])
    row_limit = pos[:, None] + jnp.arange(t)[None, :]        # (B, T)
    valid = ki[None, None, :] <= row_limit[:, :, None]       # (B, T, K)
    s = jnp.where(valid[:, None, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhtgk,bkhd->bthgd", p, v.astype(jnp.float32))
    return o.reshape(b, t, hq, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block: projections + rope + cache plumbing
# ---------------------------------------------------------------------------

def init_gqa_params(key, cfg, dtype=jnp.float32):
    from repro.models.layers import dense_init

    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * dh, dtype),
        "wk": dense_init(ks[1], d, hkv * dh, dtype),
        "wv": dense_init(ks[2], d, hkv * dh, dtype),
        "wo": dense_init(ks[3], hq * dh, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def gqa_qkv(params, x: jnp.ndarray, cfg, positions: jnp.ndarray,
            rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None):
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = jnp.einsum("bsd,df->bsf", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,df->bsf", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,df->bsf", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, hq, dh)
    k = k.reshape(b, s, hkv, dh)
    v = v.reshape(b, s, hkv, dh)
    # rope tables depend only on positions — decode hot loops hoist them
    # out of the per-layer body and pass them in
    cos, sin = rope if rope is not None else rope_freqs(
        dh, cfg.rope_theta, positions)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def gqa_block_kv(params, x: jnp.ndarray, cfg, *, causal=True,
                 chunk_q: Optional[int] = None,
                 backend: Optional[str] = None):
    """Like :func:`gqa_block` but also returns (k, v) for prefill caching."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = gqa_qkv(params, x, cfg, positions)
    o = gqa_attention(q, k, v, causal=causal, chunk_q=chunk_q,
                      pv_bf16=cfg.pv_bf16, backend=backend)
    out = jnp.einsum("bsf,fd->bsd", o.reshape(b, s, -1),
                     params["wo"].astype(x.dtype))
    return out, (k, v)


def gqa_block(params, x: jnp.ndarray, cfg, *, causal=True,
              chunk_q: Optional[int] = None,
              backend: Optional[str] = None) -> jnp.ndarray:
    return gqa_block_kv(params, x, cfg, causal=causal, chunk_q=chunk_q,
                        backend=backend)[0]


def gqa_decode_block(params, x: jnp.ndarray, cfg, cache: dict,
                     pos: jnp.ndarray, *, attend_len: Optional[int] = None,
                     backend: Optional[str] = None):
    """x: (B, 1, d).  cache: {'k': (B,Smax,Hkv,D), 'v': ...}.  pos: (B,)."""
    b = x.shape[0]
    q, k, v = gqa_qkv(params, x, cfg, pos[:, None])
    k_cache = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(
        c, u, (p, 0, 0)))(cache["k"], k, pos)
    v_cache = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(
        c, u, (p, 0, 0)))(cache["v"], v, pos)
    o = decode_attention(q, k_cache, v_cache, pos, attend_len=attend_len,
                         backend=backend)
    out = jnp.einsum("bsf,fd->bsd", o.reshape(b, 1, -1),
                     params["wo"].astype(x.dtype))
    return out, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_block(params, x: jnp.ndarray, enc_kv: Tuple[jnp.ndarray, jnp.ndarray],
                cfg, *, backend: Optional[str] = None) -> jnp.ndarray:
    b, s, _ = x.shape
    hq, dh = cfg.n_heads, cfg.d_head
    q = jnp.einsum("bsd,df->bsf", x, params["wq"].astype(x.dtype))
    q = q.reshape(b, s, hq, dh)
    k, v = enc_kv
    o = gqa_attention(q, k, v, causal=False, backend=backend)
    return jnp.einsum("bsf,fd->bsd", o.reshape(b, s, -1),
                      params["wo"].astype(x.dtype))


def encode_cross_kv(params, enc_out: jnp.ndarray, cfg):
    b, s, _ = enc_out.shape
    hkv, dh = cfg.n_kv_heads, cfg.d_head
    k = jnp.einsum("bsd,df->bsf", enc_out, params["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,df->bsf", enc_out, params["wv"].astype(enc_out.dtype))
    return k.reshape(b, s, hkv, dh), v.reshape(b, s, hkv, dh)


# ---------------------------------------------------------------------------
# MLA (MiniCPM3 / DeepSeek-V2): latent-compressed KV
# ---------------------------------------------------------------------------

def init_mla_params(key, cfg, dtype=jnp.float32):
    from repro.models.layers import dense_init

    d, h = cfg.d_model, cfg.n_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "q_down": dense_init(ks[0], d, qr, dtype),
        "q_norm": jnp.ones((qr,), dtype),
        "q_up": dense_init(ks[1], qr, h * (nd + rd), dtype),
        "kv_down": dense_init(ks[2], d, kr + rd, dtype),
        "kv_norm": jnp.ones((kr,), dtype),
        "kv_up": dense_init(ks[3], kr, h * (nd + vd), dtype),
        "wo": dense_init(ks[4], h * vd, d, dtype),
    }


def _mla_qkv(params, x, cfg, positions):
    from repro.models.layers import rmsnorm

    b, s, _ = x.shape
    h = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank
    cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, params["q_down"].astype(x.dtype)),
                 params["q_norm"])
    q = jnp.einsum("bsr,rf->bsf", cq, params["q_up"].astype(x.dtype))
    q = q.reshape(b, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    ckv = jnp.einsum("bsd,dr->bsr", x, params["kv_down"].astype(x.dtype))
    latent, k_rope = ckv[..., :kr], ckv[..., kr:]
    latent = rmsnorm(latent, params["kv_norm"])
    cos, sin = rope_freqs(rd, cfg.rope_theta, positions)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return q_nope, q_rope, latent, k_rope


def mla_block_kv(params, x: jnp.ndarray, cfg, *, causal=True,
                 chunk_q: Optional[int] = None,
                 backend: Optional[str] = None):
    """Like :func:`mla_block` but also returns (latent, k_rope) for prefill."""
    b, s, _ = x.shape
    h = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    positions = jnp.arange(s)
    q_nope, q_rope, latent, k_rope = _mla_qkv(params, x, cfg, positions)
    kv = jnp.einsum("bsr,rf->bsf", latent, params["kv_up"].astype(x.dtype))
    kv = kv.reshape(b, s, h, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, rd))], axis=-1)
    # MLA rides the shared dispatch: the kernel supports Dv != D directly
    o = gqa_attention(q, k, v, causal=causal, chunk_q=chunk_q,
                      pv_bf16=cfg.pv_bf16, backend=backend)
    out = jnp.einsum("bsf,fd->bsd", o.reshape(b, s, -1),
                     params["wo"].astype(x.dtype))
    return out, (latent, k_rope)


def mla_block(params, x: jnp.ndarray, cfg, *, causal=True,
              chunk_q: Optional[int] = None,
              backend: Optional[str] = None) -> jnp.ndarray:
    """Training/prefill: decompress the latent into per-head K/V (naive form)."""
    return mla_block_kv(params, x, cfg, causal=causal, chunk_q=chunk_q,
                        backend=backend)[0]


def mla_decode_block(params, x: jnp.ndarray, cfg, cache: dict,
                     pos: jnp.ndarray, *, attend_len: Optional[int] = None):
    """Absorbed-matmul decode: attention runs in the latent space, so the
    cache stores only (latent, k_rope) — the MLA serving trick.  Cache:
    {'latent': (B, Smax, kr), 'rope': (B, Smax, rd)}.  attend_len bounds
    the scored prefix (see :func:`decode_attention`)."""
    b = x.shape[0]
    h = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank
    q_nope, q_rope, latent, k_rope = _mla_qkv(params, x, cfg, pos[:, None])
    lat_cache = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(
        c, u, (p, 0)))(cache["latent"], latent, pos)
    rope_cache = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(
        c, u, (p, 0)))(cache["rope"], k_rope, pos)
    lat_read, rope_read = lat_cache, rope_cache
    if attend_len is not None and attend_len < lat_cache.shape[1]:
        lat_read = lat_cache[:, :attend_len]
        rope_read = rope_cache[:, :attend_len]
    kv_up = params["kv_up"].reshape(kr, h, nd + vd)
    w_uk, w_uv = kv_up[..., :nd], kv_up[..., nd:]
    # absorb W_uk into the query:  q' = q_nope @ W_uk^T  -> latent space
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk.astype(x.dtype))
    scale = (nd + rd) ** -0.5
    s_lat = jnp.einsum("bhr,bkr->bhk", q_lat[:, 0].astype(jnp.float32),
                       lat_read.astype(jnp.float32))
    s_rope = jnp.einsum("bhr,bkr->bhk", q_rope[:, 0].astype(jnp.float32),
                        rope_read.astype(jnp.float32))
    s = (s_lat + s_rope) * scale
    smax = lat_read.shape[1]
    ki = jnp.arange(smax)
    s = jnp.where((ki[None, :] <= pos[:, None])[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhk,bkr->bhr", p, lat_read.astype(jnp.float32))
    o = jnp.einsum("bhr,rhv->bhv", ctx_lat,
                   w_uv.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bf,fd->bd", o.reshape(b, -1),
                     params["wo"].astype(x.dtype))[:, None, :]
    return out, {"latent": lat_cache, "rope": rope_cache}
