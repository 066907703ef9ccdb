"""The GQA mixture-of-experts family (Qwen2-MoE, as Qwen2-57B-A14B):
the dense family's attention (RMSNorm, half-split rotary positions,
Q/K/V projections with bias, grouped-query causal softmax), then a
sparse expert block in every layer, and an untied output head.  The
contract of each exported name is in ``bench/families/__init__.py``.

The expert block (``Qwen2MoeSparseMoeBlock``): a router over all
``n_experts``, the softmax over them, the top ``top_k`` probabilities
kept as weights (renormalised over the k only where ``norm_topk_prob``),
each expert a SwiGLU of width ``d_ff``; plus a shared SwiGLU of width
``shared_ff`` scaled by ``sigmoid(x . w_shared_gate)``.

A configuration holds one chip's share of the experts under expert
parallelism: experts ``[expert_offset, expert_offset + experts_held)``.
The router keeps its published width and top-k, and the layer adds only
the held experts' part of the routed result (the absent experts' part
belongs to the chips that hold them), then the shared expert whole.  The
reference computes the same share.

Weights: the tree follows the layout the program's MoE model reads,
``embed``, ``ln_f``, ``lm_head`` and ``layers`` with ``ln1``, ``ln2``,
``attn`` (``wq wk wv wo bq bk bv``) and ``moe`` (``router`` (d, E),
``w_gate w_up`` (held, d, f), ``w_down`` (held, f, d), ``shared``
(``w_gate w_up w_down``) and ``shared_gate`` (d, 1)).  Each expert's
leaves are keyed by its global expert id, so a share of the experts is a
slice of the whole layer's.

Reference: it follows the published description and imports nothing of
the program; the attention, the rotary positions and the head are the
dense family's.  Each held expert is computed for every row and weighted
by the row's router weight for it (zero where the expert is not among
its top k): plain, and exact for the share.  ``gaps`` and the float8
control work as in the dense family.
"""

from __future__ import annotations

import functools
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.families import dense_gqa
from bench.families.dense_gqa import (BLOCK, Q_BLOCK, _head_gaps, _pad,
                                      _rms, _rope, kv_token_bytes)
from bench.precision import HIGHEST, mm
from bench.work import BYTES, causal_keys

# the dense family's attention work counts hold here unchanged
decode_kernel = dense_gqa.decode_kernel
flash_kernel = dense_gqa.flash_kernel
attention_layers = dense_gqa.attention_layers


def dims(conf: dict) -> dict:
    """The configuration's keys as run; ``num_experts`` there counts the
    experts held, the router's width is the published count."""
    c = conf["config"]
    heads = c["num_attention_heads"]
    published = conf.get("reduced", {})
    return {
        "n_layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "n_heads": heads,
        "n_kv_heads": c["num_key_value_heads"],
        "d_head": c.get("head_dim", c["hidden_size"] // heads),
        "d_ff": c["moe_intermediate_size"],
        "shared_ff": c["shared_expert_intermediate_size"],
        "n_experts": published.get("num_experts", c["num_experts"]),
        "experts_held": c["num_experts"],
        "expert_offset": conf.get("expert_offset", 0),
        "top_k": c["num_experts_per_tok"],
        "norm_topk_prob": bool(c["norm_topk_prob"]),
        "vocab": c["vocab_size"],
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "rope_theta": float(c["rope_theta"]),
    }


def program_config(conf: dict, dims: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=conf["name"], family="moe", n_layers=dims["n_layers"],
        d_model=dims["d_model"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_head=dims["d_head"],
        d_ff=dims["d_ff"], vocab=dims["vocab"], qkv_bias=True,
        rope_theta=dims["rope_theta"], norm_eps=dims["norm_eps"],
        tie_embeddings=dims["tie_embeddings"],
        n_experts=dims["n_experts"], top_k=dims["top_k"],
        expert_offset=dims["expert_offset"],
        experts_held=dims["experts_held"],
        shared_expert_ff=dims["shared_ff"],
        norm_topk_prob=dims["norm_topk_prob"])


# ------------------------------------------------------------- weights
# leaf ids: fixed for good, they key the random bits
_TOP = ("embed", "ln_f", "lm_head")
_LAYER = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
          "router", "w_gate", "w_up", "w_down",
          "shared_w_gate", "shared_w_up", "shared_w_down", "shared_gate")
LEAF_ID = {name: i for i, name in enumerate(_TOP + _LAYER)}
_EXPERT = ("w_gate", "w_up", "w_down")
_SHARED = ("shared_w_gate", "shared_w_up", "shared_w_down")


def leaf_specs(cfg: dict) -> dict:
    """Leaf name -> (shape of one layer's, or one expert's, leaf, kind,
    exponent)."""
    d, f, fs = cfg["d_model"], cfg["d_ff"], cfg["shared_ff"]
    dense = weights.matrix
    out = {n: spec for n, spec in dense_gqa.leaf_specs(
        dict(cfg, tie_embeddings=False)).items()
        if n not in ("w_gate", "w_up", "w_down")}
    out.update({
        "router": dense(d, cfg["n_experts"]),
        "w_gate": dense(d, f), "w_up": dense(d, f), "w_down": dense(f, d),
        "shared_w_gate": dense(d, fs), "shared_w_up": dense(d, fs),
        "shared_w_down": dense(fs, d), "shared_gate": dense(d, 1),
    })
    if cfg["tie_embeddings"]:
        del out["lm_head"]
    return out


def layer_leaves(key, cfg: dict, layer, dtype):
    """Layer ``layer`` in the program's nesting; expert ``e``'s leaves
    are keyed by ``layer * n_experts + e``."""
    spec = leaf_specs(cfg)
    g = lambda n, at=layer: weights.leaf(key, LEAF_ID, n, at, spec[n],
                                         dtype)
    held = cfg["expert_offset"] + jnp.arange(cfg["experts_held"],
                                             dtype=jnp.uint32)
    at = layer * np.uint32(cfg["n_experts"]) + held
    experts = {n: jax.vmap(lambda e, n=n: g(n, e))(at) for n in _EXPERT}
    return {"ln1": g("ln1"), "ln2": g("ln2"),
            "attn": {n: g(n) for n in ("wq", "wk", "wv", "wo",
                                       "bq", "bk", "bv")},
            "moe": {"router": g("router"), **experts,
                    "shared": {n[len("shared_"):]: g(n) for n in _SHARED},
                    "shared_gate": g("shared_gate")}}


def top_leaves(key, cfg: dict, dtype):
    spec = leaf_specs(cfg)
    return {n: weights.leaf(key, LEAF_ID, n, 0, spec[n], dtype)
            for n in _TOP if n in spec}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The whole tree, on the default device, in one jitted call, one
    layer's random bits alive at a time."""
    key = weights.base_key(seed)

    @jax.jit
    def build(key):
        params = top_leaves(key, cfg, dtype)
        params["layers"] = jax.lax.map(
            lambda l: layer_leaves(key, cfg, l, dtype),
            jnp.arange(cfg["n_layers"], dtype=jnp.uint32))
        return params

    return jax.block_until_ready(build(key))


# ----------------------------------------------------------- reference
def _swiglu(x, w_gate, w_up, w_down, control):
    h = jax.nn.silu(mm(x, w_gate, control)) * mm(x, w_up, control)
    return mm(h, w_down, control)


def routed(g, p, cfg: dict, control: bool):
    """The held experts' part of the expert block for rows ``g`` (S, d)
    after the layer's second norm."""
    probs = jax.nn.softmax(mm(g, p["router"], control), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["top_k"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)

    def expert(y, j):
        w = jnp.sum(jnp.where(idx == cfg["expert_offset"] + j, top, 0.0),
                    axis=-1)
        out = _swiglu(g, p["w_gate"][j], p["w_up"][j], p["w_down"][j],
                      control)
        return y + w[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(g),
                        jnp.arange(cfg["experts_held"]))
    return y


def shared(g, p, control: bool):
    """The shared expert, scaled by its sigmoid gate."""
    s = p["shared"]
    gate = jax.nn.sigmoid(mm(g, p["shared_gate"], control))
    return gate * _swiglu(g, s["w_gate"], s["w_up"], s["w_down"], control)


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _layer(h, p, *, cfg, control):
    """One decoder layer over a whole (S, d) sequence."""
    cfg = dict(cfg)
    s = h.shape[0]
    hq, hkv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    g = _rms(h, p["ln1"], cfg["norm_eps"])
    a = p["attn"]
    q = (mm(g, a["wq"], control) + a["bq"]).reshape(s, hq, dh)
    k = (mm(g, a["wk"], control) + a["bk"]).reshape(s, hkv, dh)
    v = (mm(g, a["wv"], control) + a["bv"]).reshape(s, hkv, dh)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=1)                     # (S, Hq, D)
    v = jnp.repeat(v, hq // hkv, axis=1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * dh ** -0.5
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where((jnp.arange(s)[None, :] <= rows[:, None])[None],
                       sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(s // Q_BLOCK)).reshape(s, hq * dh)
    h = h + mm(o, a["wo"], control)
    g = _rms(h, p["ln2"], cfg["norm_eps"])
    return (h + routed(g, p["moe"], cfg, control)
            + shared(g, p["moe"], control))


@functools.partial(jax.jit, static_argnums=(1,))
def _top(key, cfg):
    """Embedding, final norm and untied head in bf16, as served (every
    value is exact in bf16)."""
    return top_leaves(key, dict(cfg), jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1,))
def _layer_weights(key, cfg, layer):
    return layer_leaves(key, dict(cfg), layer, jnp.float32)


def logits(cfg: dict, seed: int, tokens) -> np.ndarray:
    """Full (S, vocab) float32 logits of one short sequence (tests)."""
    key = weights.base_key(seed)
    frozen = tuple(sorted(cfg.items()))
    top = jax.tree.map(lambda x: x.astype(jnp.float32), _top(key, frozen))
    n = len(tokens)
    ids = np.zeros(_pad(n, BLOCK), np.int32)
    ids[:n] = tokens
    h = top["embed"][jnp.asarray(ids)]
    for layer in range(cfg["n_layers"]):
        h = _layer(h, _layer_weights(key, frozen, np.uint32(layer)),
                   cfg=frozen, control=False)
    head = top["lm_head"] if "lm_head" in top else top["embed"].T
    h = _rms(h[:n], top["ln_f"], cfg["norm_eps"])
    return np.asarray(jnp.matmul(h, head, precision=HIGHEST))


def gaps(cfg: dict, seed: int, seqs, *, control: bool = False):
    """As the dense family's ``gaps``: per sequence, the gap of each
    served token, and of the float8 forward's first choices when asked
    for ``control``."""
    key = weights.base_key(seed)
    frozen = tuple(sorted(cfg.items()))
    top = _top(key, frozen)
    hs = []
    for toks, _ in seqs:
        ids = np.zeros(_pad(len(toks), BLOCK), np.int32)
        ids[:len(toks)] = toks
        hs.append(top["embed"][jnp.asarray(ids)].astype(jnp.float32))
    ctl = list(hs)
    for layer in range(cfg["n_layers"]):
        p = _layer_weights(key, frozen, np.uint32(layer))
        hs = [_layer(h, p, cfg=frozen, control=False) for h in hs]
        if control:
            ctl = [_layer(h, p, cfg=frozen, control=True) for h in ctl]
        del p
    table = top["lm_head"] if "lm_head" in top else top["embed"]
    ln_f = top["ln_f"].astype(jnp.float32)
    sound, ctl_gaps = [], []
    for i, (toks, n_prompt) in enumerate(seqs):
        n = len(toks)
        nxt = np.zeros(hs[i].shape[0], np.int32)
        nxt[:n - 1] = toks[1:]
        rows = slice(n_prompt - 1, n - 1)
        g = _head_gaps(hs[i], table, ln_f, jnp.asarray(nxt), hs[i],
                       cfg=frozen, control=False)
        sound.append(np.asarray(g)[rows])
        if control:
            g = _head_gaps(hs[i], table, ln_f, jnp.asarray(nxt), ctl[i],
                           cfg=frozen, control=True)
            ctl_gaps.append(np.asarray(g)[rows])
    return sound, (ctl_gaps if control else None)


# ---------------------------------------------- operations and bytes
def _attention_weights(m) -> int:
    d, hq, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d


def _expert_weights(m) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def _rows_per_token(m) -> float:
    """(token, held expert) pairs per token, routing spread evenly."""
    return m["top_k"] * m["experts_held"] / m["n_experts"]


def _experts_hit(m, n: int) -> float:
    """Held experts that ``n`` tokens reach, routing spread evenly."""
    miss = (1.0 - m["top_k"] / m["n_experts"]) ** n
    return m["experts_held"] * (1.0 - miss)


def _dense_weights(m) -> int:
    """Matrix weights of a layer that every token reads: attention, the
    router, the shared expert and its gate."""
    d = m["d_model"]
    return (_attention_weights(m) + d * m["n_experts"]
            + 3 * d * m["shared_ff"] + d)


def _token_flops(m) -> float:
    """Matrix operations of one token through every layer and the head
    (attention scores and values apart)."""
    per_layer = (_dense_weights(m)
                 + _rows_per_token(m) * _expert_weights(m))
    return 2.0 * (m["n_layers"] * per_layer + m["d_model"] * m["vocab"])


def _weight_bytes(m, n: int) -> float:
    """What ``n`` tokens read of the parameters: every layer's dense part
    (with biases and norms), the held experts they reach, the head and
    the final norm."""
    d, hq, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    per_layer = (_dense_weights(m) + (hq + 2 * hkv) * dh + 2 * d
                 + _experts_hit(m, n) * _expert_weights(m))
    return BYTES * (m["n_layers"] * per_layer + d * m["vocab"] + d)


def _attn(m, keys: float) -> float:
    return 4.0 * m["n_layers"] * m["n_heads"] * m["d_head"] * keys


def decode_step(m, ctx: Iterable[int]) -> Tuple[float, float]:
    """One decode token for each live row; ``ctx`` = keys each row
    attends.  Expert rows and weights as even routing gives them."""
    ctx = list(ctx)
    flops = _token_flops(m) * len(ctx) + _attn(m, sum(ctx))
    nbytes = (_weight_bytes(m, len(ctx))
              + kv_token_bytes(m) * (sum(ctx) + len(ctx)))
    return flops, float(nbytes)


def prefill(m, n: int, start: int = 0) -> Tuple[float, float]:
    """A prompt's ``n`` uncached tokens after ``start`` cached ones: all
    layers, causal attention, the head on the last token only."""
    flops = ((_token_flops(m) - 2.0 * m["d_model"] * m["vocab"]) * n
             + _attn(m, causal_keys(n, start))
             + 2.0 * m["d_model"] * m["vocab"])
    nbytes = _weight_bytes(m, n) + kv_token_bytes(m) * (start + 2 * n)
    return flops, float(nbytes)


def expert_kernel(m, rows: float, experts_hit: float) -> Tuple[float, float]:
    """The grouped matmul's calls (gate, up and down projections) over
    ``rows`` (token, held expert) pairs that reach ``experts_hit`` held
    experts: each hit expert's weights read once, each row's activations
    read and written (d in, f out twice; f in, d out)."""
    d, f = m["d_model"], m["d_ff"]
    flops = 2.0 * rows * _expert_weights(m)
    nbytes = BYTES * (experts_hit * _expert_weights(m) + rows * 3 * (d + f))
    return flops, float(nbytes)
