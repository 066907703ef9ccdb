"""Compile guards for the real chip: the main-path Pallas kernels at
qwen2-1.5b widths (12 query heads, 2 KV heads, head dim 128, bf16,
batch 8), AOT-compiled for a described TPU v5e.  Interpret mode checks
no tiling or fast-memory (VMEM) rule; the chip's compiler, installed
here, does — so a refusal shows up in this file instead of on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and the test
workers all import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import (decode_attention_op,
                                                paged_decode_attention_op)
from repro.kernels.flash_attention.ops import flash_mha
from repro.kernels.rmsnorm.ops import rmsnorm_op
from repro.kernels.verify_attention.ops import paged_verify_attention_op

B, HQ, HKV, D = 8, 12, 2, 128            # qwen2-1.5b attention widths
D_MODEL = 1536
CACHE = 2048                              # cached tokens per slot


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep these out of any persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_shapes(page_size, kv_dtype):
    pages = CACHE // page_size * B + 1
    shapes = [((B, 1, HQ, D), jnp.bfloat16),
              ((pages, page_size, HKV, D), kv_dtype),
              ((pages, page_size, HKV, D), kv_dtype),
              ((B, CACHE // page_size), jnp.int32),
              ((B,), jnp.int32)]
    if kv_dtype == jnp.int8:
        shapes += [((pages, page_size), jnp.float32)] * 2
    return shapes


def test_flash_decode_compiles(one_chip):
    text = _compile(
        lambda q, k, v, pos: decode_attention_op(q, k, v, pos,
                                                 interpret=False),
        one_chip, ((B, 1, HQ, D), jnp.bfloat16),
        ((B, CACHE, HKV, D), jnp.bfloat16),
        ((B, CACHE, HKV, D), jnp.bfloat16), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page_size,kv_dtype", [
    (16, jnp.bfloat16), (128, jnp.bfloat16), (16, jnp.int8)])
def test_paged_flash_decode_compiles(one_chip, page_size, kv_dtype):
    def fn(q, kp, vp, bt, pos, *scales):
        return paged_decode_attention_op(q, kp, vp, bt, pos, *scales,
                                         interpret=False)

    text = _compile(fn, one_chip, *_paged_shapes(page_size, kv_dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_flash_verify_compiles(one_chip, kv_dtype):
    shapes = _paged_shapes(16, kv_dtype)
    shapes[0] = ((B, 4, HQ, D), jnp.bfloat16)       # T=4 window

    def fn(q, kp, vp, bt, pos, *scales):
        return paged_verify_attention_op(q, kp, vp, bt, pos, *scales,
                                         interpret=False)

    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("seq", [1024, 200])
def test_flash_forward_compiles(one_chip, seq):
    text = _compile(
        lambda q, k, v, n: flash_mha(q, k, v, kv_valid_len=n,
                                     interpret=False),
        one_chip, ((2, seq, HQ, D), jnp.bfloat16),
        ((2, seq, HKV, D), jnp.bfloat16), ((2, seq, HKV, D), jnp.bfloat16),
        ((2,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_backward_compiles(one_chip):
    def grads(q, k, v):
        loss = lambda *a: flash_mha(*a, interpret=False).astype(
            jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    shape = [((2, 512, HQ, D), jnp.bfloat16),
             ((2, 512, HKV, D), jnp.bfloat16),
             ((2, 512, HKV, D), jnp.bfloat16)]
    text = _compile(grads, one_chip, *shape)
    # forward + dq + dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("rows", [B, 2 * 2048])
def test_rmsnorm_compiles(one_chip, rows):
    text = _compile(lambda x, w: rmsnorm_op(x, w, interpret=False),
                    one_chip, ((rows, D_MODEL), jnp.bfloat16),
                    ((D_MODEL,), jnp.bfloat16))
    assert "tpu_custom_call" in text
