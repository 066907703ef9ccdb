"""The dense GQA decoder family (Qwen2 and Qwen1.5): RMSNorm, rotary
positions with the half-split rotation, Q/K/V projections with bias,
grouped-query causal softmax attention, SwiGLU MLP, tied or untied
output head.  The contract of each exported name is in
``bench/families/__init__.py``.

Weights: the tree follows the layout the program's dense model reads,
``embed``, ``ln_f``, ``lm_head`` (untied only) and ``layers`` with
``ln1``, ``ln2``, ``attn`` (``wq wk wv wo bq bk bv``) and ``mlp``
(``w_gate w_up w_down``), each layer leaf stacked on a leading axis.

Reference: it follows the published description and imports nothing of
the program.  Its weights are made again from the seed, one layer at a
time, so a model that does not fit the chip in float32 still runs.
``gaps`` runs whole sequences (prompt plus served tokens) through the
model and reports, at every position whose next token was served, how
far the served token's logit lies below the reference's best logit.
The control (``control=True``) computes the same forward with every
matrix product's inputs rounded to float8 (``precision.mm``); at each
position it reads the gap of the token that the float8 forward puts
first.
"""

from __future__ import annotations

import functools
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.precision import HIGHEST, fp8, mm
from bench.work import BYTES, causal_keys


def dims(conf: dict) -> dict:
    """The published config's keys, as the weights and the reference
    read them."""
    c = conf["config"]
    heads = c["num_attention_heads"]
    return {
        "n_layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "n_heads": heads,
        "n_kv_heads": c["num_key_value_heads"],
        "d_head": c.get("head_dim", c["hidden_size"] // heads),
        "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "rope_theta": float(c["rope_theta"]),
    }


def program_config(conf: dict, dims: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=conf["name"], family="dense", n_layers=dims["n_layers"],
        d_model=dims["d_model"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_head=dims["d_head"],
        d_ff=dims["d_ff"], vocab=dims["vocab"], qkv_bias=True,
        rope_theta=dims["rope_theta"], norm_eps=dims["norm_eps"],
        tie_embeddings=dims["tie_embeddings"])


def attention_layers(dims: dict) -> int:
    """Every layer calls the paged decode kernel once a step."""
    return dims["n_layers"]


# ------------------------------------------------------------- weights
# leaf ids: fixed for good, they key the random bits
_TOP = ("embed", "ln_f", "lm_head")
_LAYER = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
          "w_gate", "w_up", "w_down")
LEAF_ID = {name: i for i, name in enumerate(_TOP + _LAYER)}


def leaf_specs(cfg: dict) -> dict:
    """Leaf name -> (per-layer shape, kind, exponent) for ``cfg`` (keys
    ``d_model n_heads n_kv_heads d_head d_ff vocab tie_embeddings``)."""
    d, hq, hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    dh, f, v = cfg["d_head"], cfg["d_ff"], cfg["vocab"]
    dense = weights.matrix
    out = {
        "embed": ((v, d), "int", weights.exponent(0.02)),
        "ln_f": ((d,), "norm", 7),
        "ln1": ((d,), "norm", 7),
        "ln2": ((d,), "norm", 7),
        "wq": dense(d, hq * dh), "wk": dense(d, hkv * dh),
        "wv": dense(d, hkv * dh), "wo": dense(hq * dh, d),
        "bq": ((hq * dh,), "int", weights.exponent(0.05)),
        "bk": ((hkv * dh,), "int", weights.exponent(0.05)),
        "bv": ((hkv * dh,), "int", weights.exponent(0.05)),
        "w_gate": dense(d, f), "w_up": dense(d, f), "w_down": dense(f, d),
    }
    if not cfg["tie_embeddings"]:
        out["lm_head"] = dense(d, v)
    return out


def layer_leaves(key, cfg: dict, layer, dtype):
    """Layer ``layer`` in the program's nesting."""
    spec = leaf_specs(cfg)
    g = lambda n: weights.leaf(key, LEAF_ID, n, layer, spec[n], dtype)
    return {"ln1": g("ln1"), "ln2": g("ln2"),
            "attn": {n: g(n) for n in ("wq", "wk", "wv", "wo",
                                       "bq", "bk", "bv")},
            "mlp": {n: g(n) for n in ("w_gate", "w_up", "w_down")}}


def top_leaves(key, cfg: dict, dtype):
    spec = leaf_specs(cfg)
    return {n: weights.leaf(key, LEAF_ID, n, 0, spec[n], dtype)
            for n in ("embed", "ln_f", "lm_head") if n in spec}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The whole tree, on the default device, in one jitted call.  Layers
    are made one after another (``lax.map``), so only one layer's random
    bits are alive at a time."""
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
BLOCK = 512             # shortest padded sequence
Q_BLOCK = 512           # query rows per attention block
HEAD_ROWS = 256         # rows of logits per block
VOCAB_BLOCK = 8192      # vocabulary columns per block


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, D); rotate-half form, position = row index."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv     # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=1)                           # (S, Hq, D)
    v = jnp.repeat(v, rep, axis=1)
    scale = dh ** -0.5

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = jnp.arange(s)[None, :] <= rows[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(s // Q_BLOCK))
    o = o.reshape(s, hq * dh)
    h = h + mm(o, a["wo"], control)
    g = _rms(h, p["ln2"], cfg["norm_eps"])
    m = p["mlp"]
    y = jax.nn.silu(mm(g, m["w_gate"], control)) * mm(g, m["w_up"],
                                                      control)
    return h + mm(y, m["w_down"], control)


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _head_gaps(h, table, ln_f, nxt, h_ctl, *, cfg, control):
    """Per row: best reference logit minus the reference logit of the
    token under test (``nxt``, or the control's own first choice).

    ``table`` is the bf16 output matrix as stored: the (V, d) embedding
    when tied, the (d, V) head when not.  The vocabulary is walked in
    blocks converted to float32 one at a time, so no float32 copy of the
    whole matrix is ever made."""
    cfg = dict(cfg)
    eps, tied, vocab = cfg["norm_eps"], cfg["tie_embeddings"], cfg["vocab"]
    vb = min(VOCAB_BLOCK, vocab)
    amax = jnp.max(jnp.abs(table)).astype(jnp.float32)

    def columns(start):
        if tied:
            return jax.lax.dynamic_slice_in_dim(
                table, start, vb, 0).astype(jnp.float32).T
        return jax.lax.dynamic_slice_in_dim(
            table, start, vb, 1).astype(jnp.float32)

    def column_of(tok):                        # (R,) -> (R, d)
        if tied:
            return table[tok].astype(jnp.float32)
        return table[:, tok].T.astype(jnp.float32)

    def rows(i):
        hb = _rms(jax.lax.dynamic_slice_in_dim(h, i * HEAD_ROWS, HEAD_ROWS,
                                               0), ln_f, eps)
        cb = _rms(jax.lax.dynamic_slice_in_dim(h_ctl, i * HEAD_ROWS,
                                               HEAD_ROWS, 0), ln_f, eps)
        cq = fp8(cb)

        def block(carry, j):
            best, cbest, carg = carry
            start = jnp.minimum(j * vb, vocab - vb)
            w = columns(start)
            best = jnp.maximum(best, jnp.max(
                jnp.matmul(hb, w, precision=HIGHEST), -1))
            if control:
                lg = jnp.matmul(cq, fp8(w, amax), precision=HIGHEST)
                m = jnp.max(lg, -1)
                arg = start + jnp.argmax(lg, -1)
                carg = jnp.where(m > cbest, arg, carg)
                cbest = jnp.maximum(cbest, m)
            return (best, cbest, carg), None

        neg = jnp.full((HEAD_ROWS,), -jnp.inf, jnp.float32)
        (best, _, carg), _ = jax.lax.scan(
            block, (neg, neg, jnp.zeros((HEAD_ROWS,), jnp.int32)),
            jnp.arange(-(-vocab // vb)))
        tok = carg if control else jax.lax.dynamic_slice_in_dim(
            nxt, i * HEAD_ROWS, HEAD_ROWS, 0)
        return best - jnp.sum(hb * column_of(tok), -1)

    return jax.lax.map(rows, jnp.arange(h.shape[0] // HEAD_ROWS)).reshape(-1)


@functools.partial(jax.jit, static_argnums=(1,))
def _top(key, cfg):
    """Embedding, final norm and untied head in bf16, as served (every
    value is exact in bf16)."""
    return top_leaves(key, dict(cfg), jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1,))
def _layer_weights(key, cfg, layer):
    return layer_leaves(key, dict(cfg), layer, jnp.float32)


def _pad(n: int, block: int) -> int:
    """Sequence length bucket: a power of two, at least ``block``, so a
    run's sample compiles a handful of shapes (kept in the persistent
    cache).  Padding sits after the real tokens, where causal attention
    keeps it out of every real row."""
    size = block
    while size < n:
        size *= 2
    return size


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
    """``seqs``: list of (tokens, n_prompt) — the prompt followed by the
    served tokens.  Returns ``(sound, control)``: per sequence, a float32
    array of the gap at each served token (row i belongs to served token
    i); ``control`` (None unless asked for) holds the gaps of the float8
    forward's first choices at the same positions."""
    key = weights.base_key(seed)
    frozen = tuple(sorted(cfg.items()))
    top = _top(key, frozen)
    hs, ctl = [], []
    for toks, _ in seqs:
        ids = np.zeros(_pad(len(toks), BLOCK), np.int32)
        ids[:len(toks)] = toks
        x = top["embed"][jnp.asarray(ids)].astype(jnp.float32)
        hs.append(x)
        ctl.append(x)
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
        # logits at position p predict token p + 1: served tokens are
        # toks[n_prompt:], predicted at rows n_prompt - 1 .. n - 2
        rows = slice(n_prompt - 1, n - 1)
        g = _head_gaps(hs[i], table, ln_f, jnp.asarray(nxt), hs[i],
                       cfg=frozen, control=False)
        sound.append(np.asarray(g)[rows])
        if control:
            g = _head_gaps(hs[i], table, ln_f, jnp.asarray(nxt),
                           ctl[i], cfg=frozen, control=True)
            ctl_gaps.append(np.asarray(g)[rows])
    return sound, (ctl_gaps if control else None)


# ---------------------------------------------- operations and bytes
def layer_weights(m) -> int:
    """Matrix weights of one layer."""
    d, hq, hkv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["d_head"], m["d_ff"])
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f


def weight_bytes(m) -> int:
    """Every parameter the forward pass reads: layers (with biases and
    norms), the head, the final norm."""
    d, hq, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    per_layer = layer_weights(m) + (hq + 2 * hkv) * dh + 2 * d
    head = d * m["vocab"]
    return BYTES * (m["n_layers"] * per_layer + head + d)


def kv_token_bytes(m) -> int:
    """K and V of one token over all layers."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["d_head"] * BYTES


def _attn(m, keys: float) -> float:
    """Score and value products over all layers, for query rows that see
    ``keys`` keys in all."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["d_head"] * keys


def decode_step(m, ctx: Iterable[int]) -> Tuple[float, float]:
    """One decode token for each live row; ``ctx`` = keys each row
    attends (its position + 1)."""
    ctx = list(ctx)
    flops = (2.0 * (m["n_layers"] * layer_weights(m)
                    + m["d_model"] * m["vocab"]) * len(ctx)
             + _attn(m, sum(ctx)))
    nbytes = weight_bytes(m) + kv_token_bytes(m) * (sum(ctx) + len(ctx))
    return flops, float(nbytes)


def decode_kernel(m, ctx: Iterable[int]) -> Tuple[float, float]:
    """Paged decode attention over all layers of one step: reads each
    row's K/V once, reads q and writes o."""
    ctx = list(ctx)
    qo = 2 * m["n_layers"] * m["n_heads"] * m["d_head"] * BYTES * len(ctx)
    return (_attn(m, sum(ctx)),
            float(kv_token_bytes(m) * sum(ctx) + qo))


def prefill(m, n: int, start: int = 0) -> Tuple[float, float]:
    """A prompt's ``n`` uncached tokens after ``start`` cached ones: all
    layers, causal attention over what each row sees, the head on the
    last token only."""
    flops = (2.0 * m["n_layers"] * layer_weights(m) * n
             + _attn(m, causal_keys(n, start))
             + 2.0 * m["d_model"] * m["vocab"])
    nbytes = (weight_bytes(m) + kv_token_bytes(m) * (start + 2 * n))
    return flops, float(nbytes)


def flash_kernel(m, n: int) -> Tuple[float, float]:
    """Causal flash attention over one ``n``-token prompt, all layers:
    q, k, v read once, o written once."""
    rows = (2 * m["n_heads"] + 2 * m["n_kv_heads"]) * m["d_head"]
    return (_attn(m, causal_keys(n)),
            float(m["n_layers"] * n * rows * BYTES))
