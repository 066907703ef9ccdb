"""The paged decode/verify kernel gathers its own pages (interpret mode).

The kernel reads a stacked (L, P, page, Hkv, D) pool at a layer index,
copies each row's live pages ``pages_per_block`` at a time, and gives a
free row (its table starts at the trash page) no work.  Every case here
is checked against the gather oracles of ``ref.py`` on the same layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.decode_attention import pages_per_block
from repro.kernels.decode_attention.ops import paged_decode_attention_op
from repro.kernels.decode_attention.ref import paged_decode_attention_ref
from repro.kernels.verify_attention.ops import paged_verify_attention_op
from repro.kernels.verify_attention.ref import paged_verify_attention_ref
from repro.models.attention import (paged_decode_attention,
                                    paged_verify_attention)
from repro.serve.kv_cache import TRASH_PAGE, quantize_kv_rows

L, P, PAGE, HQ, D, NB = 3, 48, 8, 4, 32, 9


def _pools(dtype, hkv, seed=0):
    """Stacked pools whose layers differ; int8 pools carry scales."""
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(L, P, PAGE, hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, P, PAGE, hkv, D)), jnp.float32)
    if dtype == jnp.int8:
        (k, ks), (v, vs) = quantize_kv_rows(k), quantize_kv_rows(v)
        return k, v, ks, vs
    return k.astype(dtype), v.astype(dtype), None, None


def _block(hkv, t, dtype):
    return PAGE * pages_per_block(NB, PAGE, t * (HQ // hkv), hkv, D,
                                  jnp.dtype(dtype).itemsize)


def _tables(n_rows, seed=1):
    """Distinct live pages per row (page 0, the trash page, never)."""
    rng = np.random.default_rng(seed)
    pages = 1 + rng.permutation(P - 1)[:n_rows * NB]
    return jnp.asarray(pages.reshape(n_rows, NB), jnp.int32)


def _ref(q, pools, bt, pos, layer, t):
    k, v, ks, vs = pools
    one = lambda x: None if x is None else x[layer].astype(jnp.float32)
    if t == 1:
        b, _, hq, d = q.shape
        hkv = k.shape[3]
        out = paged_decode_attention_ref(
            q.astype(jnp.float32).reshape(b, hkv, hq // hkv, d), one(k),
            one(v), bt, pos, k_scales=one(ks), v_scales=one(vs))
        return out.reshape(b, 1, hq, d)
    return paged_verify_attention_ref(q.astype(jnp.float32), one(k),
                                      one(v), bt, pos, k_scales=one(ks),
                                      v_scales=one(vs))


def _kernel(q, pools, bt, pos, layer, t, lengths=None):
    k, v, ks, vs = pools
    op = paged_decode_attention_op if t == 1 else paged_verify_attention_op
    return op(q, k, v, bt, pos, ks, vs, layer=layer, lengths=lengths,
              interpret=True)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2, jnp.int8: 2e-5}


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("dtype,hkv", [
    (jnp.float32, 2),      # heads indexed in the block
    (jnp.bfloat16, 2),     # heads read as 32-bit words, low half / high half
    (jnp.bfloat16, 1),     # page axis second-minor: heads-major block
    (jnp.int8, 2),         # heads-major block, scales in score space
    (jnp.int8, 4),
])
def test_gather_matches_oracle_at_every_length(dtype, hkv, t):
    """Row lengths of 1 key, one page, one compute block, one past a
    block and the whole table (whose 9 pages are no multiple of the
    block's), read at layer 2 of a stacked pool."""
    block = _block(hkv, t, dtype)
    assert NB % (block // PAGE)
    lengths = [1, PAGE, block, block + 1, NB * PAGE]
    pos = jnp.asarray([n - t for n in lengths], jnp.int32)
    pos = jnp.maximum(pos, 0)
    pools = _pools(dtype, hkv)
    bt = _tables(len(lengths))
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (len(lengths), t, HQ, D)).astype(
        jnp.float32 if dtype == jnp.int8 else dtype)
    got = _kernel(q, pools, bt, pos, 2, t)
    want = _ref(q, pools, bt, pos, 2, t)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])
    # another layer is another answer
    other = _kernel(q, pools, bt, pos, 1, t)
    assert not np.allclose(np.asarray(other, np.float32),
                           np.asarray(got, np.float32))


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
def test_free_rows_and_uncovered_pages_change_nothing(dtype, t):
    """Rows whose table is all trash, with a runaway position, read
    nothing: the live rows stay bit-identical, every output is finite,
    and NaN in every page (or scale row) no live row covers — the trash
    page among them — and in the tails past each live row's last key
    changes nothing."""
    hkv = 2
    pools = _pools(dtype, hkv)
    live_bt = _tables(3)
    live_pos = jnp.asarray([0, 2 * PAGE + 3, NB * PAGE - t], jnp.int32)
    trash = jnp.full((2, NB), TRASH_PAGE, jnp.int32)
    bt = jnp.concatenate([live_bt[:1], trash[:1], live_bt[1:], trash[1:]])
    pos = jnp.concatenate([live_pos[:1], jnp.asarray([10 ** 6]),
                           live_pos[1:], jnp.asarray([NB * PAGE + 5])])
    live = np.array([True, False, True, True, False])
    q = jax.random.normal(jax.random.PRNGKey(3), (5, t, HQ, D)).astype(
        jnp.float32 if dtype == jnp.int8 else dtype)
    fn = paged_decode_attention if t == 1 else paged_verify_attention
    k, v, ks, vs = pools

    def run(k, v, ks, vs):
        return np.asarray(fn(q, k, v, bt, pos, layer=1, k_scales=ks,
                             v_scales=vs, backend="kernel"), np.float32)

    base = run(k, v, ks, vs)
    alone = np.asarray(fn(q[live], k, v, bt[live], pos[live], layer=1,
                          k_scales=ks, v_scales=vs, backend="kernel"),
                       np.float32)
    np.testing.assert_array_equal(base[live], alone)
    assert np.all(np.isfinite(base))

    covered = np.zeros((P, PAGE), bool)
    for row in np.flatnonzero(live):
        n = int(pos[row]) + t
        for j in range(-(-n // PAGE)):
            covered[int(bt[row, j]), :min(PAGE, n - j * PAGE)] = True
    poison = jnp.asarray(~covered)[None, :, :, None, None]
    if dtype == jnp.int8:
        ks = jnp.where(poison[..., 0, 0], jnp.nan, ks)
        vs = jnp.where(poison[..., 0, 0], jnp.nan, vs)
    else:
        k = jnp.where(poison, jnp.asarray(jnp.nan, k.dtype), k)
        v = jnp.where(poison, jnp.asarray(jnp.nan, v.dtype), v)
    got = run(k, v, ks, vs)
    np.testing.assert_array_equal(got[live], base[live])
    assert np.all(np.isfinite(got))


def test_pages_per_block_follows_the_shapes():
    """A power of two, never past the table, never past the fast-memory
    budget: more query rows or wider rows give smaller blocks."""
    for nb in (1, 3, 9, 72):
        ppb = pages_per_block(nb, 128, 6, 2, 128, 2)
        assert ppb & (ppb - 1) == 0 and 1 <= ppb <= nb
    assert pages_per_block(72, 128, 6, 2, 128, 2) == 16    # qwen2-1.5b
    assert pages_per_block(41, 128, 5, 8, 128, 2) == 8     # qwen1.5-32b
    assert (pages_per_block(72, 128, 5, 8, 128, 2)
            < pages_per_block(72, 128, 6, 2, 128, 2))
    assert (pages_per_block(72, 128, 1536, 2, 128, 2)
            < pages_per_block(72, 128, 6, 2, 128, 2))
