"""Differentiable flash attention Pallas TPU kernels (forward + backward).

The online-softmax running max / running sum are exactly the paper's
warp-reduce pattern applied per query row: they live in VMEM scratch across
the KV grid axis and never round-trip to HBM (the HW path).  The SW-path
comparison point is the naive materialized-scores attention in ``ref.py``
and the chunked jnp lowering in ``models/attention.py``.

Three kernels share one masking discipline (causal + per-batch valid
length, so right-padded prefill batches are exact):

  forward   grid (bh, q_blocks, kv_blocks), kv innermost "arbitrary" so the
            (m, l, acc) scratch carries across kv steps.  Emits the output
            and the per-row logsumexp residual ``lse = m + log(l)`` that the
            backward pass needs to rebuild probabilities without a second
            softmax sweep.
  dq        same grid; rebuilds p = exp(s - lse) per block, accumulates
            dq += (p * (dp - delta)) @ k in scratch.
  dk/dv     grid (bh, kv_blocks, q_blocks), q innermost; accumulates
            dv += p^T @ dO and dk += ds^T @ q in scratch.

Causal block-skip: kv blocks strictly above the diagonal are never
computed (``pl.when``) *and* never fetched — the kv index map clamps the
block index at the diagonal (and at the valid-length bound), so the Pallas
pipeline re-addresses the previous block instead of streaming a new one.
That halves both compute and K/V HBM traffic for causal attention, the
same work-scales-with-valid-data discipline as decode's valid-length skip.

VMEM per fwd step (fp32): bq*d + bk*(d+dv) + bq*bk + bq*(d+2) floats —
with bq=bk=128, d=dv=128: ~260 KB, comfortably under ~16 MB/core.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# lse stand-in for fully-masked rows: large positive so exp(s - lse)
# underflows to exactly 0 in the backward rebuild
FULLY_MASKED_LSE = 0.7 * float(jnp.finfo(jnp.float32).max)
# the forward kernel's op name on the chip (HLO instruction and trace)
FWD_KERNEL_NAME = "flash_attention_fwd_kernel"


def _last_kv_block(kv_len, block_k: int, kv_steps: int):
    """Index of the last kv block holding any in-length position."""
    return jnp.clip(pl.cdiv(kv_len, block_k) - 1, 0, kv_steps - 1)


def _score_mask(qi, kj, kv_len, block_q: int, block_k: int, causal: bool):
    """(block_q, block_k) bool: True where the score entry is live."""
    k_ids = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_ids < kv_len
    if causal:
        q_ids = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = valid & (q_ids >= k_ids)
    return valid


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
                block_q: int, block_k: int, kv_steps: int, block_skip: bool):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    kv_len = kv_len_ref[b]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = kj * block_k < kv_len
    if causal and block_skip:
        live = live & (kj * block_k <= qi * block_q + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _score_mask(qi, kj, kv_len, block_q, block_k, causal)
        s = jnp.where(valid, s, DEFAULT_MASK_VALUE)

        m_prev = m_scr[...]                          # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # lane-axis reduce
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # explicit zeroing (not just exp underflow) keeps l exact for rows
        # whose every entry in this block is masked
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)             # (bk, dv)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == kv_steps - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(safe)
        # (bq, 1) column -> the (1, bq) lane-major row the residual is
        # stored as
        lse_ref[0] = jnp.where(l == 0.0, FULLY_MASKED_LSE, lse).T


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        kv_len: Optional[jnp.ndarray] = None, *,
                        causal: bool = True, scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        block_skip: bool = True,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q: (bh, sq, d); k: (bh, skv, d); v: (bh, skv, dv); kv_len: (bh,) int32.

    Returns (o (bh, sq, dv), lse (bh, sq) fp32).  Heads are pre-flattened
    into the batch axis (GQA expansion happens in ``ops.flash_mha``).
    Sequence lengths must divide the (clamped) block sizes — the ops
    wrapper pads and masks via ``kv_len``.
    """
    from repro.kernels.common import default_interpret

    if interpret is None:
        interpret = default_interpret()
    bh, sq, d = q.shape
    skv = k.shape[1]
    dv = v.shape[-1]
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    q_steps = pl.cdiv(sq, block_q)
    kv_steps = pl.cdiv(skv, block_k)
    if kv_len is None:
        kv_len = jnp.full((bh,), skv, jnp.int32)

    def kv_im(b, i, j, kv_len_ref):
        if block_skip:
            if causal:
                j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
            j = jnp.minimum(j, _last_kv_block(kv_len_ref[b], block_k,
                                              kv_steps))
        return (b, j, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_steps=kv_steps, block_skip=block_skip)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, q_steps, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, ref: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_im, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), kv_im, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j, ref: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j, ref: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the op's name on the chip's trace, inside a layer scan too
        name=FWD_KERNEL_NAME,
    )(kv_len.astype(jnp.int32), q, k, v)
    return o, lse[:, 0]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Forward-only compat wrapper: q/k/v (bh, s, d) -> o (bh, sq, d)."""
    return flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)[0]


# ---------------------------------------------------------------------------
# Backward: dq pass (grid like forward, kv innermost)
# ---------------------------------------------------------------------------

def _dq_kernel(kv_len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_scr, *, scale: float, causal: bool, block_q: int,
               block_k: int, kv_steps: int):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    kv_len = kv_len_ref[b]

    @pl.when(kj == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = kj * block_k < kv_len
    if causal:
        live = live & (kj * block_k <= qi * block_q + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # (bq, d)
        k = k_ref[0].astype(jnp.float32)             # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _score_mask(qi, kj, kv_len, block_q, block_k, causal)
        s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
        lse = lse_ref[0].T                           # (bq, 1)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)  # (bq, bk)
        do = do_ref[0].astype(jnp.float32)           # (bq, dv)
        v = v_ref[0].astype(jnp.float32)             # (bk, dv)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0].T                       # (bq, 1)
        ds = p * (dp - delta) * scale                # (bq, bk)
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == kv_steps - 1)
    def _finalize():
        dq_ref[0] = acc_scr[...]


# ---------------------------------------------------------------------------
# Backward: dk/dv pass (kv blocks outer, q innermost)
# ---------------------------------------------------------------------------

def _dkv_kernel(kv_len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float, causal: bool,
                block_q: int, block_k: int, q_steps: int):
    b = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    kv_len = kv_len_ref[b]

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = kj * block_k < kv_len
    if causal:
        live = live & (qi * block_q + block_q - 1 >= kj * block_k)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # (bq, d)
        k = k_ref[0].astype(jnp.float32)             # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _score_mask(qi, kj, kv_len, block_q, block_k, causal)
        s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
        lse = lse_ref[0].T
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)  # (bq, bk)
        do = do_ref[0].astype(jnp.float32)           # (bq, dv)
        v = v_ref[0].astype(jnp.float32)             # (bk, dv)
        dv_scr[...] += jax.lax.dot_general(          # p^T @ dO -> (bk, dv)
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0].T
        ds = p * (dp - delta) * scale
        dk_scr[...] += jax.lax.dot_general(          # ds^T @ q -> (bk, d)
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == q_steps - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...]
        dv_ref[0] = dv_scr[...]


def flash_attention_bwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        do: jnp.ndarray, lse: jnp.ndarray,
                        delta: jnp.ndarray,
                        kv_len: Optional[jnp.ndarray] = None, *,
                        causal: bool = True, scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None):
    """dq/dk/dv (fp32) from the saved (lse, delta) residuals.

    delta = rowsum(dO * O) — the standard recomputation trick that avoids
    materializing p in the forward pass.
    """
    from repro.kernels.common import default_interpret

    if interpret is None:
        interpret = default_interpret()
    bh, sq, d = q.shape
    skv = k.shape[1]
    dv = v.shape[-1]
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    q_steps = pl.cdiv(sq, block_q)
    kv_steps = pl.cdiv(skv, block_k)
    if kv_len is None:
        kv_len = jnp.full((bh,), skv, jnp.int32)
    kv_len = kv_len.astype(jnp.int32)
    # per-row residuals ride as (bh, 1, sq) so a (1, 1, block_q) block
    # meets the TPU tiling rule (a (1, block_q) block of (bh, sq) cannot)
    lse = lse.reshape(bh, 1, sq)
    delta = delta.reshape(bh, 1, sq)

    # ---- dq: (bh, q_blocks, kv_blocks), kv innermost ----
    def kv_im(b, i, j, kv_len_ref):
        if causal:
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        j = jnp.minimum(j, _last_kv_block(kv_len_ref[b], block_k, kv_steps))
        return (b, j, 0)

    def q_row_im(b, i, j, ref):
        return (b, 0, i)

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, q_steps, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, ref: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_im, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), kv_im, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, dv), lambda b, i, j, ref: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), q_row_im, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), q_row_im, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, i, j, ref: (b, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          kv_steps=kv_steps),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_len, q, k, v, do, lse, delta)

    # ---- dk/dv: (bh, kv_blocks, q_blocks), q innermost ----
    def q_im(b, j, i, kv_len_ref):
        if causal:
            i = jnp.maximum(i, (j * block_k) // block_q)
        return (b, i, 0)

    def q_row_im2(b, j, i, kv_len_ref):
        if causal:
            i = jnp.maximum(i, (j * block_k) // block_q)
        return (b, 0, i)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, kv_steps, q_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_im, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, j, i, ref: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), lambda b, j, i, ref: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, dv), q_im, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), q_row_im2,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), q_row_im2,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i, ref: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), lambda b, j, i, ref: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
    )
    dk, dv_out = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, q_steps=q_steps),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, skv, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_len, q, k, v, do, lse, delta)
    return dq, dk, dv_out
