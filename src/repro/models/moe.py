"""Mixture-of-Experts layers: the router, the held-expert layer that
serving runs, and the GShard capacity dispatch that training runs.

Both route over all ``cfg.n_experts`` (the router's published width) and
compute only the experts this chip holds, ``[cfg.expert_offset,
cfg.expert_offset + cfg.n_held)``: under expert parallelism each chip
adds its own experts' share of the result, and the share of the experts
held elsewhere is theirs to add.  A gated shared expert
(``cfg.shared_expert_ff``), which every chip holds, is added whole.
Router weights are the softmax over all experts taken at the top k,
renormalised over the k where ``cfg.norm_topk_prob``.

* :func:`held_experts` (serving: prefill, decode, verify) is dropless.
  The (token, expert) pairs that land on held experts are sorted by
  expert and run through the grouped matmul (``repro.kernels.
  grouped_matmul``), so each token's result is independent of the batch
  and of its padding.  Padding rows and free slots route nothing.
* :func:`moe_block` (training) dispatches through a grouped
  capacity-based one-hot einsum: tokens are grouped per sequence, each
  group has capacity C = S * top_k * cf / E, and the dispatch/combine
  tensors (G, S, E, C) shard cleanly — G over the data axes, E over the
  model axis — with XLA inserting the all-to-alls.  A token over capacity
  is dropped.

Expert selection in the training path is the production consumer of the
paper's vote/match primitives (k rounds of argmax-extract ==
ballot-mask-out; see ``repro.kernels.moe_gating``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, swiglu


def gating_topk(logits: jnp.ndarray, top_k: int, backend: str = "hw",
                normalize: bool = True):
    """Top-k selection as iterated vote/ballot rounds.

    logits: (..., E).  Returns (weights (..., E), mask (..., E) bool) —
    softmax over the selected experts (``normalize``), or the softmax over
    all experts kept at the selected ones.  The 'hw' path vectorizes the
    rounds; the 'sw' path would serialize them (the PR-transformed form is
    exercised in benchmarks; model forward uses the vectorized semantics
    for both)."""
    x = logits.astype(jnp.float32)
    remaining = x
    selected = jnp.zeros(x.shape, dtype=bool)
    for _ in range(top_k):
        mx = jnp.max(remaining, axis=-1, keepdims=True)      # lane reduce
        hit = remaining == mx
        first = jnp.cumsum(hit.astype(jnp.int32), axis=-1) == 1
        hit = hit & first                                     # match-any tie-break
        selected = selected | hit
        remaining = jnp.where(hit, -1e30, remaining)
    if not normalize:
        return jnp.where(selected, jax.nn.softmax(x, axis=-1), 0.0), selected
    masked = jnp.where(selected, x, -1e30)
    p = jax.nn.softmax(masked, axis=-1)
    p = jnp.where(selected, p, 0.0)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return p, selected


def init_moe_params(key, cfg, dtype=jnp.float32):
    """The router over all ``n_experts``, the held experts' SwiGLU
    weights, and the shared expert with its gate where the config has
    one."""
    d, e, f = cfg.d_model, cfg.n_held, cfg.d_ff
    ks = jax.random.split(key, 4)
    scale = (2.0 / (d + f)) ** 0.5
    params = {
        "router": dense_init(ks[0], d, cfg.n_experts, dtype),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * scale).astype(dtype),
    }
    if cfg.shared_expert_ff:
        fs = cfg.shared_expert_ff
        kg, ku, kd, kr = jax.random.split(jax.random.fold_in(key, 1), 4)
        params["shared"] = {"w_gate": dense_init(kg, d, fs, dtype),
                            "w_up": dense_init(ku, d, fs, dtype),
                            "w_down": dense_init(kd, fs, d, dtype)}
        params["shared_gate"] = dense_init(kr, d, 1, dtype)
    return params


def shared_expert(params, x: jnp.ndarray) -> jnp.ndarray:
    """``sigmoid(x . w_shared_gate) * SwiGLU_shared(x)``; x (..., d)."""
    sp = params["shared"]
    gate = jax.nn.sigmoid(jnp.einsum(
        "...d,dg->...g", x, params["shared_gate"].astype(x.dtype)))
    return gate * swiglu(x, sp["w_gate"], sp["w_up"], sp["w_down"])


def route(logits: jnp.ndarray, top_k: int, normalize: bool):
    """logits (T, E) -> (weights (T, k) float32, experts (T, k) int32):
    the softmax over all experts at the top k, renormalised over the k
    where ``normalize``."""
    x = logits.astype(jnp.float32)
    vals, idx = jax.lax.top_k(x, top_k)
    if normalize:
        return jax.nn.softmax(vals, axis=-1), idx
    return jnp.exp(vals - jax.nn.logsumexp(x, axis=-1, keepdims=True)), idx


def _grouped_matmul(lhs, rhs, sizes, layer, backend: Optional[str]):
    """The grouped matmul's two lowerings: the Pallas kernel
    (``backend='kernel'``, the default on TPU), which reads a stacked
    ``rhs`` at ``layer`` where it lives, or ``lax.ragged_dot``."""
    if backend is None:
        backend = "kernel" if jax.default_backend() == "tpu" else "jnp"
    if backend == "kernel":
        from repro.kernels.grouped_matmul.ops import grouped_matmul_op

        return grouped_matmul_op(lhs, rhs, sizes, layer)
    if layer is not None:
        rhs = rhs[layer]
    return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), sizes)


# what held_experts counts, summed over tokens: (token, held expert) pairs
# computed, held experts with at least one, the largest held expert's pairs
MOE_STATS = ("moe_routed_rows", "moe_experts_hit", "moe_rows_max")


def held_experts(params, x: jnp.ndarray, cfg, *, layer=None,
                 valid: Optional[jnp.ndarray] = None,
                 backend: Optional[str] = None):
    """The dropless expert layer over the held experts (serving).

    params: one layer's MoE leaves, or with ``layer`` (an int or a traced
    index) every layer's, stacked, of which layer ``layer`` runs.  x:
    (B, S, d); valid: (B, S) bool, False for padding rows and free slots
    (they route nothing), or None (every row).  Returns (y (B, S, d),
    stats (3,) int32 in :data:`MOE_STATS` order)."""
    mats = {n: params[n] for n in ("w_gate", "w_up", "w_down")}
    if layer is not None:
        params = jax.tree.map(lambda a: a[layer], params)
    gmm = lambda lhs, n, sizes: _grouped_matmul(lhs, mats[n], sizes, layer,
                                                backend)
    with jax.named_scope("moe"):
        b, s, d = x.shape
        t, k, held = b * s, cfg.top_k, cfg.n_held
        xf = x.reshape(t, d)
        logits = jnp.einsum("td,de->te", xf, params["router"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
        weights, experts = route(logits, k, cfg.norm_topk_prob)
        local = experts - cfg.expert_offset                      # (T, k)
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine = mine & valid.reshape(t, 1)
        # pairs sorted by held expert; the rest (group ``held``) last
        group = jnp.where(mine, local, held).reshape(-1)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((held,), jnp.int32).at[group].add(1, mode="drop")
        rows = xf[order // k]
        h = jax.nn.silu(gmm(rows, "w_gate", sizes)) * gmm(rows, "w_up", sizes)
        out = gmm(h, "w_down", sizes)
        # rows past the held pairs are no group's: unspecified, so select
        routed = jnp.arange(t * k) < jnp.sum(sizes)
        out = jnp.where(routed[:, None], out, 0)
        unsort = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
        per_pair = out[unsort].reshape(t, k, d).astype(jnp.float32)
        y = jnp.einsum("tkd,tk->td", per_pair, jnp.where(mine, weights, 0.0))
        if cfg.shared_expert_ff:
            y = y + shared_expert(params, xf).astype(jnp.float32)
        stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                           jnp.max(sizes)]).astype(jnp.int32)
        return y.astype(x.dtype).reshape(b, s, d), stats


def moe_block(params, x: jnp.ndarray, cfg, *,
              capacity_factor: Optional[float] = None) -> jnp.ndarray:
    """x: (B, S, d) — B is the group axis (one group per sequence).

    With ``cfg.moe_group_size = g > 0`` and S > g, the sequence is split
    into token groups of g before dispatch (GShard grouping).  The dispatch
    tensor is (G, g, E, C) with C = g*k*cf/E, i.e. total size B*S*g*k*cf —
    *linear* in S — instead of the ungrouped B*S^2*k*cf, which is quadratic
    and is what blows up 32k-token prefill.
    """
    b, s, d = x.shape
    g = cfg.moe_group_size
    if g and s > g and s % g == 0:
        xg = x.reshape(b * (s // g), g, d)
        y = _moe_dispatch(params, xg, cfg, capacity_factor).reshape(b, s, d)
    else:
        y = _moe_dispatch(params, x, cfg, capacity_factor)
    if cfg.shared_expert_ff:
        y = y + shared_expert(params, x)
    return y


def _moe_dispatch(params, x: jnp.ndarray, cfg,
                  capacity_factor: Optional[float] = None) -> jnp.ndarray:
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cf = capacity_factor or cfg.capacity_factor
    cap = max(int(s * k * cf / e), 1)

    logits = jnp.einsum("bsd,de->bse", x, params["router"].astype(x.dtype))
    weights, mask = gating_topk(logits, k,          # (B, S, E)
                                normalize=cfg.norm_topk_prob)
    if cfg.n_held != e:
        # route over all experts, dispatch to the held ones only
        held = slice(cfg.expert_offset, cfg.expert_offset + cfg.n_held)
        weights, mask = weights[..., held], mask[..., held]

    # position of each token within its expert's capacity buffer
    pos_in_expert = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1  # (B,S,E)
    keep = mask & (pos_in_expert < cap)
    # dispatch tensor (B, S, E, C): one-hot over capacity slots
    disp = keep[..., None] & (
        pos_in_expert[..., None] == jnp.arange(cap)[None, None, None, :])
    disp_f = disp.astype(x.dtype)
    combine = disp_f * weights[..., None].astype(x.dtype)

    xe = jnp.einsum("bsec,bsd->ebcd", disp_f, x)    # (E, B, C, d)
    g = jnp.einsum("ebcd,edf->ebcf", xe, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ebcd,edf->ebcf", xe, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ebcf,efd->ebcd", h, params["w_down"].astype(x.dtype))
    y = jnp.einsum("bsec,ebcd->bsd", combine, ye)
    return y


def aux_load_balance_loss(logits: jnp.ndarray, mask: jnp.ndarray,
                          n_experts: int) -> jnp.ndarray:
    """Switch-style auxiliary loss: E * sum(f_i * p_i)."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    f = jnp.mean(mask.astype(jnp.float32), axis=(0, 1))   # fraction per expert
    pbar = jnp.mean(p, axis=(0, 1))
    return n_experts * jnp.sum(f * pbar)
