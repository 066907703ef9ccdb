"""Compile guards for the real chip: the main-path Pallas kernels at
qwen2-1.5b widths (12 query heads, 2 KV heads, head dim 128, bf16,
batch 8), the paged decode and verify kernels at the benchmark
configurations' own widths, a two-layer paged decode step, and the
grouped matmul and a two-layer expert step at Qwen2-57B-A14B's widths,
AOT-compiled for a described TPU v5e.  Interpret mode checks no tiling or
fast-memory (VMEM) rule; the chip's compiler, installed here, does — so a
refusal shows up in this file instead of on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and the test
workers all import this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import (decode_attention_op,
                                                paged_decode_attention_op)
from repro.kernels.flash_attention.ops import flash_mha
from repro.kernels.grouped_matmul.ops import KERNEL_NAME, grouped_matmul_op
from repro.kernels.rmsnorm.ops import rmsnorm_op
from repro.kernels.verify_attention.ops import paged_verify_attention_op
from repro.models.config import ModelConfig
from repro.models.lm import Model

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


# the benchmark configurations' attention and pool widths: slots, query and
# KV heads, pages of 128 tokens, the largest attention bucket; and the
# model widths a two-layer step needs
SERVED = {
    "qwen2-1.5b": dict(b=64, hq=12, hkv=2, pages=2000, bucket=9216,
                       d_model=1536, d_ff=8960, vocab=151936, tied=True),
    "qwen1.5-32b-8L": dict(b=64, hq=40, hkv=8, pages=1000, bucket=5248,
                           d_model=5120, d_ff=27392, vocab=152064,
                           tied=False),
    "qwen2-57b-a14b-7L": dict(b=128, hq=28, hkv=4, pages=1780, bucket=5248,
                              d_model=3584, d_ff=2560, vocab=151936,
                              tied=False),
}


def _served_shapes(w, t, kv_dtype, layers=2):
    pool = (layers, w["pages"], 128, w["hkv"], D)
    shapes = [((w["b"], t, w["hq"], D), jnp.bfloat16), (pool, kv_dtype),
              (pool, kv_dtype), ((w["b"], w["bucket"] // 128), jnp.int32),
              ((w["b"],), jnp.int32), ((), jnp.int32)]
    if kv_dtype == jnp.int8:
        shapes += [(pool[:3], jnp.float32)] * 2
    return shapes


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("served", sorted(SERVED))
def test_paged_kernels_compile_at_served_widths(one_chip, served, kv_dtype,
                                                t):
    """Decode (T=1) and verify (T=4) over a stacked pool read at a
    traced layer, at each benchmark configuration's widths."""
    op = paged_decode_attention_op if t == 1 else paged_verify_attention_op

    def fn(q, kp, vp, bt, pos, layer, *scales):
        return op(q, kp, vp, bt, pos, *scales, layer=layer, interpret=False)

    text = _compile(fn, one_chip,
                    *_served_shapes(SERVED[served], t, kv_dtype))
    assert "tpu_custom_call" in text


def test_paged_verify_compiles_at_the_longest_window(one_chip):
    """A 256-token suffix-prefill window at qwen2-1.5b widths: 1536 query
    rows a KV head leave room for one page a compute block, which
    ``pages_per_block`` has to see."""
    shapes = _served_shapes(SERVED["qwen2-1.5b"], 256, jnp.bfloat16)

    def fn(q, kp, vp, bt, pos, layer):
        return paged_verify_attention_op(q, kp, vp, bt, pos, layer=layer,
                                         interpret=False)

    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)


def _pool_copies(text, w):
    """Instructions of the entry computation that materialize one layer's
    pool or more: a slice, reshape, copy or transpose of that size, or any
    value shaped as one layer's pool, (P, page, Hkv, D) or (P, page,
    Hkv * D).  (Fused computations' own slices are not materialized.)"""
    entry = text[text.index("\nENTRY"):]
    text = entry[:entry.index("\n}")]
    layer = w["pages"] * 128 * w["hkv"] * D
    views = {(w["pages"], 128, w["hkv"], D), (w["pages"], 128, w["hkv"] * D)}
    moves = {"slice", "dynamic-slice", "reshape", "copy", "copy-start",
             "transpose"}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                     r"([\w-]+)\(", line)
        if not m or m.group(3) in ("parameter", "bitcast"):
            continue
        dims = tuple(int(x) for x in m.group(2).split(",") if x)
        size = int(np.prod(dims)) if dims else 1
        if (m.group(3) in moves and size >= layer) or dims in views:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("served", sorted(SERVED))
def test_paged_decode_step_copies_no_pool(one_chip, served, monkeypatch):
    """A two-layer paged decode step over a donated stacked pool, at the
    configuration's widths, through the model's own decode path: the
    kernel reads each layer's pages where they live, so the compiled step
    holds no per-layer slice or relayout of a pool."""
    import repro.kernels.common as common

    monkeypatch.setattr(common, "default_interpret", lambda: False)
    w = SERVED[served]
    cfg = ModelConfig(name=served, family="dense", n_layers=2,
                      d_model=w["d_model"], n_heads=w["hq"],
                      n_kv_heads=w["hkv"], d_head=D, d_ff=w["d_ff"],
                      vocab=w["vocab"], qkv_bias=True,
                      tie_embeddings=w["tied"])
    model = Model(cfg, param_dtype=jnp.bfloat16,
                  compute_dtype=jnp.bfloat16, decode_backend="kernel")
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(
        w["b"], w["bucket"], layout="paged", page_size=128,
        num_pages=w["pages"])))
    ids = jax.ShapeDtypeStruct((w["b"],), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, tok, pos: model.decode_step(
        p, c, tok, pos, attend_len=w["bucket"]), donate_argnums=1)
    text = step.lower(params, cache, ids, ids).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert _pool_copies(text, w) == []


# Qwen2-57B-A14B's expert layer on one chip of four: 16 held experts of
# d 3584 and f 2560, read from a stack of 7 layers
EXPERTS, D_EXPERT, F_EXPERT, STAGE = 16, 3584, 2560, 7


@pytest.mark.parametrize("k,n", [(D_EXPERT, F_EXPERT), (F_EXPERT, D_EXPERT)])
@pytest.mark.parametrize("rows", [1024, 8192])
def test_grouped_matmul_compiles_at_published_widths(one_chip, rows, k, n):
    """The gate/up (d -> f) and down (f -> d) projections over a decode
    step's (token, expert) rows (128 tokens x top 8) and a prefill's
    (1024 x 8), at a traced layer of the stack."""
    text = _compile(
        lambda x, w, sizes, layer: grouped_matmul_op(x, w, sizes, layer,
                                                     interpret=False),
        one_chip, ((rows, k), jnp.bfloat16),
        ((STAGE, EXPERTS, k, n), jnp.bfloat16), ((EXPERTS,), jnp.int32),
        ((), jnp.int32))
    assert "tpu_custom_call" in text and KERNEL_NAME in text


def test_expert_step_copies_no_expert_weights(one_chip, monkeypatch):
    """A two-layer MoE paged decode step at Qwen2-57B-A14B's widths: the
    grouped matmul reads each layer's experts from the stack where they
    live, so no instruction of the step materializes a layer's experts."""
    import repro.kernels.common as common

    monkeypatch.setattr(common, "default_interpret", lambda: False)
    w = SERVED["qwen2-57b-a14b-7L"]
    cfg = ModelConfig(name="qwen2-57b-a14b", family="moe", n_layers=2,
                      d_model=D_EXPERT, n_heads=w["hq"], n_kv_heads=w["hkv"],
                      d_head=D, d_ff=F_EXPERT, vocab=w["vocab"],
                      qkv_bias=True, n_experts=64, top_k=8,
                      experts_held=EXPERTS, shared_expert_ff=20480,
                      norm_topk_prob=False)
    model = Model(cfg, param_dtype=jnp.bfloat16,
                  compute_dtype=jnp.bfloat16, decode_backend="kernel")
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(
        w["b"], w["bucket"], layout="paged", page_size=128,
        num_pages=w["pages"])))
    ids = jax.ShapeDtypeStruct((w["b"],), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, tok, pos: model._gqa_decode_paged(
        p, c, tok, pos, attend_len=2048), donate_argnums=1)
    text = step.lower(params, cache, ids, ids).compile().as_text()
    assert text.count(KERNEL_NAME + " = ") + text.count(
        KERNEL_NAME + ".") >= 6
    entry = text[text.index("\nENTRY"):]
    layer = {f"[{EXPERTS},{D_EXPERT},{F_EXPERT}]",
             f"[{EXPERTS},{F_EXPERT},{D_EXPERT}]"}
    copies = [line.strip()[:160] for line in
              entry[:entry.index("\n}")].splitlines()
              if any(shape in line for shape in layer)
              and "parameter(" not in line and " bitcast(" not in line]
    assert copies == []
