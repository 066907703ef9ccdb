"""Operations and bytes on known shapes."""

import pytest

from bench import work
from bench.families import dense_gqa

M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
     "d_head": 4, "d_ff": 16, "vocab": 10}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_layer_weights():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, mlp 3 x 8x16
    assert dense_gqa.layer_weights(M) == 64 + 32 + 32 + 64 + 384


def test_decode_step_counts_rows_and_contexts():
    f, b = dense_gqa.decode_step(M, [3, 5])
    matmul = 2 * (2 * 576 + 8 * 10) * 2
    attn = 4 * 2 * 2 * 4 * (3 + 5)
    assert f == matmul + attn
    kv = 2 * 2 * 1 * 4 * 2          # bytes of one token's K and V
    assert b == dense_gqa.weight_bytes(M) + kv * (8 + 2)


def test_prefill_counts_causal_work_only():
    f, _ = dense_gqa.prefill(M, 4)
    f_cached, _ = dense_gqa.prefill(M, 4, start=6)
    attn = 4 * 2 * 2 * 4
    assert f - f_cached == attn * (0 - 4 * 6)
    assert f == 2 * 2 * 576 * 4 + attn * 10 + 2 * 8 * 10


def test_flash_and_decode_kernels():
    f, b = dense_gqa.flash_kernel(M, 3)
    assert f == 4 * 2 * 2 * 4 * 6
    assert b == 2 * 3 * (2 * 2 + 2 * 1) * 4 * 2
    f, b = dense_gqa.decode_kernel(M, [7])
    assert f == 4 * 2 * 2 * 4 * 7
    assert b == 2 * 2 * 1 * 4 * 2 * 7 + 2 * 2 * 2 * 4 * 2


@pytest.mark.parametrize("flops,nbytes,want", [(100.0, 1.0, 1.0),
                                               (1.0, 100.0, 10.0)])
def test_roofline_takes_the_larger_bound(flops, nbytes, want):
    assert work.min_seconds(flops, nbytes, PEAK) == want
