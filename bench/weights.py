"""Seeded weights for the dense GQA family, made by the benchmark.

The benchmark makes the weights and hands them to the program, so that
the reference can make the very same values again without taking
anything from the program.  Every value is an 8-bit integer times a
power of two (norm weights: 1 plus a multiple of 2**-7), so it is exact
in bfloat16 and in float32, and the served weights and the reference's
float32 copy hold identical numbers.

Each leaf of each layer has a key of its own, ``fold(fold(base, leaf),
layer)``, so the program's stacked leaves (made in one jitted call, on
the device, in bfloat16) and the reference's one-layer-at-a-time float32
leaves come from the same bits.

The tree follows the layout the program's dense GQA model reads:
``embed``, ``ln_f``, ``lm_head`` (untied only) and ``layers`` with
``ln1``, ``ln2``, ``attn`` (``wq wk wv wo bq bk bv``) and ``mlp``
(``w_gate w_up w_down``), each layer leaf stacked on a leading axis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

INT8_STD = math.sqrt((256 ** 2 - 1) / 12.0)   # std of uniform bytes

# leaf ids: fixed for good, they key the random bits
_TOP = ("embed", "ln_f", "lm_head")
_LAYER = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
          "w_gate", "w_up", "w_down")
LEAF_ID = {name: i for i, name in enumerate(_TOP + _LAYER)}


def base_key(seed: int):
    """A key from any non-negative seed, all 64 bits of it counted."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64); got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _exponent(std: float) -> int:
    """Power of two that brings uniform bytes nearest to ``std``."""
    return int(round(math.log2(INT8_STD / std)))


def dims(cfg: dict) -> dict:
    """Leaf name -> (per-layer shape, kind, exponent) for ``cfg`` (keys
    ``d_model n_heads n_kv_heads d_head d_ff vocab tie_embeddings``)."""
    d, hq, hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    dh, f, v = cfg["d_head"], cfg["d_ff"], cfg["vocab"]

    def dense(a, b):
        return ((a, b), "int", _exponent(math.sqrt(2.0 / (a + b))))

    out = {
        "embed": ((v, d), "int", _exponent(0.02)),
        "ln_f": ((d,), "norm", 7),
        "ln1": ((d,), "norm", 7),
        "ln2": ((d,), "norm", 7),
        "wq": dense(d, hq * dh), "wk": dense(d, hkv * dh),
        "wv": dense(d, hkv * dh), "wo": dense(hq * dh, d),
        "bq": ((hq * dh,), "int", _exponent(0.05)),
        "bk": ((hkv * dh,), "int", _exponent(0.05)),
        "bv": ((hkv * dh,), "int", _exponent(0.05)),
        "w_gate": dense(d, f), "w_up": dense(d, f), "w_down": dense(f, d),
    }
    if not cfg["tie_embeddings"]:
        out["lm_head"] = dense(d, v)
    return out


def leaf(key, name: str, layer, spec, dtype):
    """One layer's value of leaf ``name``; exact in bf16 and f32."""
    shape, kind, e = spec
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_ID[name]), layer)
    bits = jax.random.bits(k, shape, jnp.uint8)
    if kind == "norm":
        # 1 + j / 128, j in [-16, 16]
        j = (bits % 33).astype(jnp.float32) - 16.0
        return (1.0 + j * 2.0 ** -e).astype(dtype)
    x = jax.lax.bitcast_convert_type(bits, jnp.int8).astype(jnp.float32)
    return (x * 2.0 ** -e).astype(dtype)


def layer_leaves(key, cfg: dict, layer, dtype):
    """Layer ``layer`` in the program's nesting."""
    spec = dims(cfg)
    g = lambda n: leaf(key, n, layer, spec[n], dtype)
    return {"ln1": g("ln1"), "ln2": g("ln2"),
            "attn": {n: g(n) for n in ("wq", "wk", "wv", "wo",
                                       "bq", "bk", "bv")},
            "mlp": {n: g(n) for n in ("w_gate", "w_up", "w_down")}}


def top_leaves(key, cfg: dict, dtype):
    spec = dims(cfg)
    out = {n: leaf(key, n, 0, spec[n], dtype)
           for n in ("embed", "ln_f", "lm_head") if n in spec}
    return out


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The whole tree, on the default device, in one jitted call.  Layers
    are made one after another (``lax.map``), so only one layer's random
    bits are alive at a time."""
    key = base_key(seed)

    @jax.jit
    def build(key):
        params = top_leaves(key, cfg, dtype)
        params["layers"] = jax.lax.map(
            lambda l: layer_leaves(key, cfg, l, dtype),
            jnp.arange(cfg["n_layers"], dtype=jnp.uint32))
        return params

    return jax.block_until_ready(build(key))
