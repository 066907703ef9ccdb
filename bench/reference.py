"""Plain float32 reference of the dense GQA decoder family (Qwen2 and
Qwen1.5: RMSNorm, rotary positions with the half-split rotation, Q/K/V
projections with bias, grouped-query causal softmax attention, SwiGLU
MLP, tied or untied output head).

It follows the published description and imports nothing of the
program: its weights are made again from the seed by ``weights.py``,
one layer at a time, so a model that does not fit the chip in float32
still runs.  Every matrix product is at ``precision="highest"``; on a
TPU a float32 product is otherwise computed in bfloat16 passes.

``gaps`` runs whole sequences (prompt plus served tokens) through the
model and reports, at every position whose next token was served, how
far the served token's logit lies below the reference's best logit.

The control (``control=True``) computes the same forward with every
matrix product's inputs rounded to float8 (e4m3, one scale per tensor):
the precision one step below the configuration's bfloat16.  At each
position it reads the gap of the token that the float8 forward puts
first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512             # shortest padded sequence
Q_BLOCK = 512           # query rows per attention block
HEAD_ROWS = 256         # rows of logits per block
VOCAB_BLOCK = 8192      # vocabulary columns per block


def _fp8(x, amax=None):
    """Round to float8 e4m3 with one scale for the tensor (or for the
    whole tensor ``x`` is a block of, given its ``amax``)."""
    if amax is None:
        amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, control: bool):
    if control:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


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
    q = (_mm(g, a["wq"], control) + a["bq"]).reshape(s, hq, dh)
    k = (_mm(g, a["wk"], control) + a["bk"]).reshape(s, hkv, dh)
    v = (_mm(g, a["wv"], control) + a["bv"]).reshape(s, hkv, dh)
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
    h = h + _mm(o, a["wo"], control)
    g = _rms(h, p["ln2"], cfg["norm_eps"])
    m = p["mlp"]
    y = jax.nn.silu(_mm(g, m["w_gate"], control)) * _mm(g, m["w_up"],
                                                        control)
    return h + _mm(y, m["w_down"], control)


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
        cq = _fp8(cb)

        def block(carry, j):
            best, cbest, carg = carry
            start = jnp.minimum(j * vb, vocab - vb)
            w = columns(start)
            best = jnp.maximum(best, jnp.max(
                jnp.matmul(hb, w, precision=HIGHEST), -1))
            if control:
                lg = jnp.matmul(cq, _fp8(w, amax), precision=HIGHEST)
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
    return weights.top_leaves(key, dict(cfg), jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1,))
def _layer_weights(key, cfg, layer):
    return weights.layer_leaves(key, dict(cfg), layer, jnp.float32)


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
    forward's first choices at the same positions.  ``cfg`` holds
    the model keys that ``weights.dims`` reads plus ``n_layers``,
    ``norm_eps`` and ``rope_theta``."""
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
