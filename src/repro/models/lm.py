"""Model assembly for all assigned architecture families.

One functional interface for every family:
  init(key)                          -> params pytree
  forward(params, batch)             -> logits (B, S, V)   [train / prefill]
  init_cache(B, max_seq)             -> cache pytree        [decode]
  decode_step(params, cache, tok, pos) -> (logits (B, V), cache)

Layers are stacked and scanned (jax.lax.scan) so the HLO stays one-layer-
sized even for 80-layer configs; remat (jax.checkpoint) bounds activation
memory during training.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models.attention import (
    cross_block,
    encode_cross_kv,
    gqa_block,
    gqa_block_kv,
    gqa_decode_block,
    init_gqa_params,
    init_mla_params,
    mla_block,
    mla_block_kv,
    mla_decode_block,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    DEFAULT_WF,
    WarpFeatureConfig,
    dense_init,
    embed_init,
    rmsnorm,
    swiglu,
)
from repro.models.moe import held_experts, init_moe_params, moe_block
from repro.models.recurrent import (
    init_mamba2_params,
    init_rwkv6_params,
    mamba2_mix,
    rwkv6_channel_mix,
    rwkv6_time_mix,
)


def _stack_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _mlp_init(key, cfg, dtype=jnp.float32):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d, f, dtype),
        "w_up": dense_init(ks[1], d, f, dtype),
        "w_down": dense_init(ks[2], f, d, dtype),
    }


class Model:
    """Family-dispatching functional model."""

    def __init__(self, cfg: ModelConfig, wf: WarpFeatureConfig = DEFAULT_WF,
                 chunk_q: Optional[int] = None, remat: bool = True,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                 act_sharding=None, remat_policy: Optional[str] = None,
                 decode_backend: Optional[str] = None,
                 attn_backend: Optional[str] = None):
        self.cfg = cfg
        self.wf = wf
        # decode attention lowering: 'kernel' (flash-decode Pallas) | 'jnp'
        # | None (auto: kernel on TPU, jnp elsewhere)
        self.decode_backend = decode_backend
        # training/prefill attention lowering: 'kernel' (differentiable
        # flash Pallas, causal block-skip) | 'jnp' (chunked softmax) |
        # None (auto: kernel on TPU, jnp elsewhere)
        self.attn_backend = attn_backend
        # chunked attention for long sequences (memory-bounded prefill)
        self.chunk_q = chunk_q
        self.remat = remat
        # remat_policy='save_attn': keep attention outputs (named
        # 'attn_out') across the backward pass — the chunked-score
        # attention is the most expensive recompute (~20% of total FLOPs
        # at S=4k) and its output is only (B, S, d).
        self.remat_policy = remat_policy
        self.param_dtype = param_dtype
        self.compute_dtype = compute_dtype
        # Optional NamedSharding for the (B, S, d) residual stream.  GSPMD's
        # propagation can lose the batch sharding through scanned layer
        # bodies and fall back to full replication ("involuntary full
        # rematerialization"); pinning the scan carry at every layer
        # boundary keeps it honest.  See EXPERIMENTS.md §Perf iteration 3.
        self.act_sharding = act_sharding

    def _pin(self, x):
        if self.act_sharding is not None and x.ndim == 3:
            return jax.lax.with_sharding_constraint(x, self.act_sharding)
        return x

    def _checkpoint(self, fn):
        if self.remat_policy == "save_attn":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.save_only_these_names(
                    "attn_out"))
        return jax.checkpoint(fn)

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict[str, Any]:
        cfg, dt = self.cfg, self.param_dtype
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": embed_init(keys[0], cfg.vocab_padded, cfg.d_model, dt),
            "ln_f": jnp.ones((cfg.d_model,), dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(keys[1], cfg.d_model,
                                           cfg.vocab_padded, dt)

        def layer_init(k):
            return self._layer_init(k, dt)

        params["layers"] = _stack_init(layer_init, keys[2], self._n_scan_layers)

        if cfg.family == "hybrid":
            params["shared_attn"] = {
                "ln1": jnp.ones((cfg.d_model,), dt),
                "attn": init_gqa_params(keys[3], cfg, dt),
                "ln2": jnp.ones((cfg.d_model,), dt),
                "mlp": _mlp_init(keys[4], cfg, dt),
            }
        if cfg.family == "encdec":
            def enc_layer_init(k):
                ks = jax.random.split(k, 2)
                return {
                    "ln1": jnp.ones((cfg.d_model,), dt),
                    "attn": init_gqa_params(ks[0], cfg, dt),
                    "ln2": jnp.ones((cfg.d_model,), dt),
                    "mlp": _mlp_init(ks[1], cfg, dt),
                }

            params["encoder"] = _stack_init(enc_layer_init, keys[5],
                                            cfg.n_encoder_layers)
            params["enc_ln_f"] = jnp.ones((cfg.d_model,), dt)
        if cfg.family == "vlm":
            # stub frontend projector: patch embeddings -> d_model
            params["vit_proj"] = dense_init(keys[6], cfg.d_model, cfg.d_model, dt)
        return params

    @property
    def _n_scan_layers(self) -> int:
        return self.cfg.n_layers

    def _layer_init(self, key, dt):
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        if cfg.family == "ssm":  # rwkv6
            return {
                "ln1": jnp.ones((cfg.d_model,), dt),
                "tm": init_rwkv6_params(ks[0], cfg, dt),
                "ln2": jnp.ones((cfg.d_model,), dt),
            }
        if cfg.family == "hybrid":  # zamba2 mamba layer
            return {
                "ln": jnp.ones((cfg.d_model,), dt),
                "mamba": init_mamba2_params(ks[0], cfg, dt),
            }
        layer = {
            "ln1": jnp.ones((cfg.d_model,), dt),
            "ln2": jnp.ones((cfg.d_model,), dt),
        }
        if cfg.attn_type == "mla":
            layer["attn"] = init_mla_params(ks[0], cfg, dt)
        else:
            layer["attn"] = init_gqa_params(ks[0], cfg, dt)
        if cfg.family == "moe":
            layer["moe"] = init_moe_params(ks[1], cfg, dt)
        else:
            layer["mlp"] = _mlp_init(ks[1], cfg, dt)
        if cfg.family == "encdec":
            kc = jax.random.fold_in(ks[1], 7)
            layer["cross"] = init_gqa_params(kc, cfg, dt)
            layer["ln_cross"] = jnp.ones((cfg.d_model,), dt)
        return layer

    # --------------------------------------------------------------- blocks
    def _tf_block(self, p, x, *, causal=True):
        cfg, wf = self.cfg, self.wf
        h = rmsnorm(x, p["ln1"], cfg.norm_eps, wf)
        if cfg.attn_type == "mla":
            att = mla_block(p["attn"], h, cfg, causal=causal,
                            chunk_q=self.chunk_q, backend=self.attn_backend)
        else:
            att = gqa_block(p["attn"], h, cfg, causal=causal,
                            chunk_q=self.chunk_q, backend=self.attn_backend)
        att = checkpoint_name(att, "attn_out")
        x = x + att
        h = rmsnorm(x, p["ln2"], cfg.norm_eps, wf)
        if cfg.family == "moe":
            y = moe_block(p["moe"], h, cfg)
        else:
            y = swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"])
        return x + y

    def _rwkv_block(self, p, x, state=None):
        cfg, wf = self.cfg, self.wf
        st_tm = None if state is None else (state["shift_tm"], state["wkv"])
        att, new_tm = rwkv6_time_mix(p["tm"], rmsnorm(x, p["ln1"],
                                                      cfg.norm_eps, wf),
                                     cfg, st_tm)
        x = x + att
        st_cm = None if state is None else state["shift_cm"]
        ffn, new_cm = rwkv6_channel_mix(p["tm"], rmsnorm(x, p["ln2"],
                                                         cfg.norm_eps, wf),
                                        cfg, st_cm)
        x = x + ffn
        new_state = {"shift_tm": new_tm[0], "wkv": new_tm[1],
                     "shift_cm": new_cm}
        return x, new_state

    def _mamba_block(self, p, x, state=None):
        cfg, wf = self.cfg, self.wf
        st = None if state is None else (state["conv"], state["ssm"])
        y, new = mamba2_mix(p["mamba"], rmsnorm(x, p["ln"], cfg.norm_eps, wf),
                            cfg, st)
        return x + y, {"conv": new[0], "ssm": new[1]}

    def _shared_attn_block(self, p, x, *, causal=True):
        cfg, wf = self.cfg, self.wf
        h = rmsnorm(x, p["ln1"], cfg.norm_eps, wf)
        x = x + gqa_block(p["attn"], h, cfg, causal=causal,
                          chunk_q=self.chunk_q, backend=self.attn_backend)
        h = rmsnorm(x, p["ln2"], cfg.norm_eps, wf)
        return x + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                          p["mlp"]["w_down"])

    # -------------------------------------------------------------- forward
    def _embed(self, params, tokens):
        x = params["embed"][tokens]
        return x.astype(self.compute_dtype)

    def _head(self, params, x):
        cfg = self.cfg
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps, self.wf)
        w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        return jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype)).astype(jnp.float32)

    def backbone(self, params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Hidden states for the token positions: (B, S, d) — no LM head.

        The train step consumes this with a vocab-chunked cross-entropy so
        the full (B, S, V) logits tensor is never materialized.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens)

        if cfg.family == "vlm":
            fe = batch["frontend_embeds"].astype(self.compute_dtype)
            fe = jnp.einsum("bnd,de->bne", fe,
                            params["vit_proj"].astype(fe.dtype))
            x = jnp.concatenate([fe, x], axis=1)

        if cfg.family == "encdec":
            enc = batch["frontend_embeds"].astype(self.compute_dtype)
            enc = self._scan_encoder(params, enc)
            x = self._scan_decoder_with_cross(params, x, enc)
        elif cfg.family == "ssm":
            x = self._scan_layers_stateful(params, x, self._rwkv_block)
        elif cfg.family == "hybrid":
            x = self._hybrid_forward(params, x)
        else:
            x = self._scan_layers(params, x, causal=True)

        if cfg.family == "vlm":  # strip frontend positions from logits
            x = x[:, batch["frontend_embeds"].shape[1]:, :]
        return x

    def forward(self, params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        logits = self._head(params, self.backbone(params, batch))
        return logits[..., :self.cfg.vocab]  # trim any vocab padding

    def _scan_layers(self, params, x, *, causal=True):
        block = lambda p, h: self._pin(self._tf_block(p, self._pin(h),
                                                      causal=causal))
        if self.remat:
            block = self._checkpoint(block)

        def body(h, p):
            return block(p, h), None

        x, _ = jax.lax.scan(body, self._pin(x), params["layers"])
        return x

    def _scan_layers_stateful(self, params, x, block_fn):
        fn = (lambda p, h: self._pin(block_fn(p, self._pin(h))[0]))
        if self.remat:
            fn = jax.checkpoint(fn)

        def body(h, p):
            return fn(p, h), None

        x, _ = jax.lax.scan(body, self._pin(x), params["layers"])
        return x

    def _hybrid_forward(self, params, x):
        cfg = self.cfg
        k = cfg.hybrid_attn_every
        n_groups = cfg.n_layers // k
        layers = jax.tree.map(
            lambda a: a.reshape((n_groups, k) + a.shape[1:]), params["layers"])
        mamba = lambda p, h: self._mamba_block(p, h)[0]
        if self.remat:
            mamba = jax.checkpoint(mamba)

        def group_body(h, group_params):
            h = self._shared_attn_block(params["shared_attn"], self._pin(h))

            def inner(hh, p):
                return mamba(p, self._pin(hh)), None

            h, _ = jax.lax.scan(inner, h, group_params)
            return self._pin(h), None

        x, _ = jax.lax.scan(group_body, self._pin(x), layers)
        return x

    def _scan_encoder(self, params, x):
        blk = lambda p, h: self._pin(
            self._shared_attn_block_generic(p, self._pin(h), causal=False))
        if self.remat:
            blk = jax.checkpoint(blk)

        def body(h, p):
            return blk(p, h), None

        x, _ = jax.lax.scan(body, self._pin(x), params["encoder"])
        return rmsnorm(x, params["enc_ln_f"], self.cfg.norm_eps, self.wf)

    def _shared_attn_block_generic(self, p, x, *, causal):
        cfg, wf = self.cfg, self.wf
        h = rmsnorm(x, p["ln1"], cfg.norm_eps, wf)
        x = x + gqa_block(p["attn"], h, cfg, causal=causal,
                          chunk_q=self.chunk_q, backend=self.attn_backend)
        h = rmsnorm(x, p["ln2"], cfg.norm_eps, wf)
        return x + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                          p["mlp"]["w_down"])

    def _scan_decoder_with_cross(self, params, x, enc):
        cfg, wf = self.cfg, self.wf

        def blk(p, h):
            g = rmsnorm(h, p["ln1"], cfg.norm_eps, wf)
            h = h + gqa_block(p["attn"], g, cfg, causal=True,
                              chunk_q=self.chunk_q, backend=self.attn_backend)
            g = rmsnorm(h, p["ln_cross"], cfg.norm_eps, wf)
            kv = encode_cross_kv(p["cross"], enc, cfg)
            h = h + cross_block(p["cross"], g, kv, cfg,
                                backend=self.attn_backend)
            g = rmsnorm(h, p["ln2"], cfg.norm_eps, wf)
            return h + swiglu(g, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                              p["mlp"]["w_down"])

        if self.remat:
            blk = jax.checkpoint(blk)

        def body(h, p):
            return self._pin(blk(p, self._pin(h))), None

        x, _ = jax.lax.scan(body, self._pin(x), params["layers"])
        return x

    # --------------------------------------------------------------- decode
    # families whose decode cache is a plain stacked (L, B, S, Hkv, D) K/V
    # pair — the ones the paged block-pool layout can host.  Recurrent
    # state (ssm/hybrid) is positionless; MLA caches latents; encdec adds
    # cross-attention leaves.  They stay dense.
    PAGED_FAMILIES = ("dense", "moe", "vlm")

    def supports_paged(self) -> bool:
        return (self.cfg.family in self.PAGED_FAMILIES
                and self.cfg.attn_type != "mla")

    def init_cache(self, batch_size: int, max_seq: int, dtype=None, *,
                   layout: str = "dense", page_size: int = 16,
                   num_pages: Optional[int] = None,
                   kv_dtype: Optional[str] = None) -> Dict[str, Any]:
        """Decode cache in the requested ``CacheLayout``.

        'dense': the classic (L, B, max_seq, H, D) pool — every slot
        reserves max_seq positions.  'paged': a shared block pool
        {"k_pages"/"v_pages": (L, num_pages, page_size, H, D)} plus
        per-slot block tables (B, ceil(max_seq/page_size)) initialized to
        the trash page; the serving engine's allocator populates them.

        kv_dtype (paged only): 'bf16' | 'int8' | None.  'int8' stores the
        pool symmetric-quantized with per-row scale leaves
        (``k_scales``/``v_scales``) — see ``repro.serve.kv_cache``.
        """
        cfg = self.cfg
        dtype = dtype or self.compute_dtype
        L = self._n_scan_layers
        b = batch_size
        if kv_dtype is not None and layout != "paged":
            raise ValueError("kv_dtype is a paged-layout axis; "
                             f"got layout={layout!r}")
        if layout == "paged":
            if not self.supports_paged():
                raise ValueError(
                    f"paged cache layout supports families "
                    f"{self.PAGED_FAMILIES} (non-MLA); got "
                    f"{cfg.family}/{cfg.attn_type}")
            from repro.serve.kv_cache import TRASH_PAGE, cdiv, init_page_pool

            if num_pages is None:
                # capacity parity with dense: one page set per slot-block
                num_pages = b * cdiv(max_seq, page_size) + 1
            cache = init_page_pool(L, num_pages, page_size, cfg.n_kv_heads,
                                   cfg.d_head, dtype, kv_dtype=kv_dtype)
            cache["block_tables"] = jnp.full(
                (b, cdiv(max_seq, page_size)), TRASH_PAGE, jnp.int32)
            return cache
        if layout != "dense":
            raise ValueError(f"unknown cache layout {layout!r}")
        if cfg.family == "ssm":
            d = cfg.d_model
            h = d // cfg.rwkv_head_size
            return {
                "shift_tm": jnp.zeros((L, b, d), dtype),
                "wkv": jnp.zeros((L, b, h, cfg.rwkv_head_size,
                                  cfg.rwkv_head_size), jnp.float32),
                "shift_cm": jnp.zeros((L, b, d), dtype),
            }
        if cfg.family == "hybrid":
            d_in = cfg.ssm_expand * cfg.d_model
            nh = d_in // cfg.ssm_head_dim
            n_groups = cfg.n_layers // cfg.hybrid_attn_every
            return {
                "conv": jnp.zeros((L, b, cfg.ssm_conv - 1,
                                   d_in + 2 * cfg.ssm_state), dtype),
                "ssm": jnp.zeros((L, b, nh, cfg.ssm_head_dim, cfg.ssm_state),
                                 jnp.float32),
                "attn_k": jnp.zeros((n_groups, b, max_seq, cfg.n_kv_heads,
                                     cfg.d_head), dtype),
                "attn_v": jnp.zeros((n_groups, b, max_seq, cfg.n_kv_heads,
                                     cfg.d_head), dtype),
            }
        if cfg.attn_type == "mla":
            return {
                "latent": jnp.zeros((L, b, max_seq, cfg.kv_lora_rank), dtype),
                "rope": jnp.zeros((L, b, max_seq, cfg.qk_rope_head_dim), dtype),
            }
        cache = {
            "k": jnp.zeros((L, b, max_seq, cfg.n_kv_heads, cfg.d_head), dtype),
            "v": jnp.zeros((L, b, max_seq, cfg.n_kv_heads, cfg.d_head), dtype),
        }
        if cfg.family == "encdec":
            cache["cross_k"] = jnp.zeros((L, b, cfg.n_frontend_tokens,
                                          cfg.n_kv_heads, cfg.d_head), dtype)
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
        return cache

    def _run_decode_layers(self, body, x, layers, cache, unroll: bool):
        """scan or unrolled layer loop for a decode step.

        The scan form keeps the HLO one-layer-sized, but its stacked cache
        output is a fresh buffer — XLA re-materializes the whole cache every
        token even when the input is donated.  The unrolled form chains
        per-layer ``.at[l].set`` updates on the original stacked leaves, so
        with a donated cache the updates alias in place (the zero-copy hot
        loop the serving engine compiles).
        """
        if not unroll:
            return jax.lax.scan(body, x, (layers, cache))
        new_cache = cache
        for l in range(self._n_scan_layers):
            p_l = jax.tree.map(lambda a: a[l], layers)
            c_l = jax.tree.map(lambda a: a[l], cache)
            x, out_c = body(x, (p_l, c_l))
            new_cache = jax.tree.map(
                lambda full, upd: full.at[l].set(upd.astype(full.dtype)),
                new_cache, out_c)
        return x, new_cache

    def decode_step(self, params, cache, tokens: jnp.ndarray,
                    pos: jnp.ndarray, attend_len: Optional[int] = None,
                    unroll: bool = False):
        """tokens: (B,) int32; pos: (B,) positions. Returns (logits, cache).

        attend_len: static bound on the valid cache prefix (must satisfy
        max(pos) < attend_len).  The serving engine buckets this to the
        live sequence length so each decode step scores only the filled
        part of the cache instead of dense-masking all of ``max_seq``.
        unroll: unroll the layer loop (see :meth:`_run_decode_layers`);
        ignored for the recurrent-state families (ssm/hybrid keep scan).

        A cache produced by ``init_cache(layout='paged')`` (detected by
        its ``k_pages`` leaf) routes to the paged step: same math, but
        K/V rows are written through the block tables into the shared
        page pool and attention gathers pages (always layer-unrolled —
        the tables are shared across layers, so a scan carry would force
        a (L, ...) copy of them).
        """
        cfg = self.cfg
        if "k_pages" in cache:
            logits, cache, _ = self._gqa_decode_paged(params, cache, tokens,
                                                      pos, attend_len)
            return logits, cache
        x = self._embed(params, tokens[:, None])

        if cfg.family == "ssm":
            def body(h, inp):
                p, st = inp
                h, new_st = self._rwkv_block(p, h, st)
                return h, new_st

            x, new_states = jax.lax.scan(
                body, x, (params["layers"],
                          {"shift_tm": cache["shift_tm"], "wkv": cache["wkv"],
                           "shift_cm": cache["shift_cm"]}))
            logits = self._head(params, x)[:, 0, :cfg.vocab]
            return logits, new_states

        if cfg.family == "hybrid":
            return self._hybrid_decode(params, cache, x, pos, attend_len)

        if cfg.attn_type == "mla":
            def body(h, inp):
                p, c = inp
                g = rmsnorm(h, p["ln1"], cfg.norm_eps, self.wf)
                att, new_c = mla_decode_block(p["attn"], g, cfg, c, pos,
                                              attend_len=attend_len)
                h = h + att
                g = rmsnorm(h, p["ln2"], cfg.norm_eps, self.wf)
                h = h + swiglu(g, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                               p["mlp"]["w_down"])
                return h, new_c

            x, new_cache = self._run_decode_layers(
                body, x, params["layers"],
                {"latent": cache["latent"], "rope": cache["rope"]}, unroll)
            return self._head(params, x)[:, 0, :cfg.vocab], new_cache

        if unroll and cfg.family in ("dense", "moe", "vlm"):
            return self._gqa_decode_unrolled(params, cache, x, pos,
                                             attend_len)

        def body(h, inp):
            p, c = inp
            g = rmsnorm(h, p["ln1"], cfg.norm_eps, self.wf)
            att, new_kv = gqa_decode_block(p["attn"], g, cfg,
                                           {"k": c["k"], "v": c["v"]}, pos,
                                           attend_len=attend_len,
                                           backend=self.decode_backend)
            h = h + att
            if cfg.family == "encdec":
                g = rmsnorm(h, p["ln_cross"], cfg.norm_eps, self.wf)
                h = h + cross_block(p["cross"], g,
                                    (c["cross_k"], c["cross_v"]), cfg)
            g = rmsnorm(h, p["ln2"], cfg.norm_eps, self.wf)
            if cfg.family == "moe":
                h = h + held_experts(p["moe"], g, cfg,
                                     backend=self.decode_backend)[0]
            else:
                h = h + swiglu(g, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                               p["mlp"]["w_down"])
            out_c = dict(new_kv)
            if cfg.family == "encdec":
                out_c["cross_k"], out_c["cross_v"] = c["cross_k"], c["cross_v"]
            return h, out_c

        x, new_cache = self._run_decode_layers(body, x, params["layers"],
                                               cache, unroll)
        return self._head(params, x)[:, 0, :cfg.vocab], new_cache

    def _gqa_decode_layers(self, params, x, positions, write_attend,
                           valid=None):
        """Shared unrolled decode/verify layer body for the GQA families.

        x: (B, S, d) embedded tokens sitting at absolute ``positions``
        (B, S) — S=1 is single-token decode, S=T a speculative verify
        window.  ``write_attend(l, q, k, v)`` owns the *only*
        layout-specific part: where the fresh K/V rows land and how the
        cache is read back (dense affine address vs paged block-table
        indirection, one query row vs a causally-masked window).  Keeping
        one loop keeps the dense, paged, and verify paths bit-identical by
        construction — a change to the layer math cannot diverge them.
        ``valid`` (B, S) marks the rows the expert layer routes (None:
        all).  Returns (x, stats): the expert layers' counts summed over
        layers, None for a model without them.
        """
        from repro.models.attention import gqa_qkv
        from repro.models.layers import rope_freqs

        cfg = self.cfg
        b, s, _ = x.shape
        rope = rope_freqs(cfg.d_head, cfg.rope_theta, positions)
        stats = None
        for l in range(self._n_scan_layers):
            p = jax.tree.map(lambda a: a[l], params["layers"])
            g = rmsnorm(x, p["ln1"], cfg.norm_eps, self.wf)
            q, k, v = gqa_qkv(p["attn"], g, cfg, positions, rope=rope)
            o = write_attend(l, q, k, v)
            x = x + jnp.einsum("bsf,fd->bsd", o.reshape(b, s, -1),
                               p["attn"]["wo"].astype(x.dtype))
            g = rmsnorm(x, p["ln2"], cfg.norm_eps, self.wf)
            if cfg.family == "moe":
                # the stacked experts, read at layer l where they live
                y, st = held_experts(params["layers"]["moe"], g, cfg,
                                     layer=l, valid=valid,
                                     backend=self.decode_backend)
                x = x + y
                stats = st if stats is None else stats + st
            else:
                x = x + swiglu(g, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                               p["mlp"]["w_down"])
        return x, stats

    def _gqa_decode_loop(self, params, x, pos, write_attend, valid=None):
        x, stats = self._gqa_decode_layers(params, x, pos[:, None],
                                           write_attend, valid)
        return self._head(params, x)[:, 0, :self.cfg.vocab], stats

    def _gqa_decode_unrolled(self, params, cache, x, pos,
                             attend_len: Optional[int]):
        """Zero-copy decode for the plain GQA-cache families.

        Per layer the fresh K/V row is scattered straight into the stacked
        (L, B, Smax, H, D) cache leaf — no per-layer (B, Smax, H, D)
        slice-out / write-back round trip, so with a donated cache the
        compiled step updates B rows in place and the attention read is the
        only cache traffic (bounded by attend_len).
        """
        from repro.models.attention import decode_attention

        ck, cv = cache["k"], cache["v"]
        bidx = jnp.arange(x.shape[0])

        def write_attend(l, q, k, v):
            nonlocal ck, cv
            ck = ck.at[l, bidx, pos].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[l, bidx, pos].set(v[:, 0].astype(cv.dtype))
            return decode_attention(q, ck[l], cv[l], pos,
                                    attend_len=attend_len,
                                    backend=self.decode_backend)

        logits, _ = self._gqa_decode_loop(params, x, pos, write_attend)
        return logits, {"k": ck, "v": cv}

    def _gqa_decode_paged(self, params, cache, tokens, pos,
                          attend_len: Optional[int]):
        """Zero-copy decode of ``tokens`` (B,) through the paged block pool.

        Per layer the fresh K/V row lands at ``(page, offset)`` resolved
        through the slot's block table — a scatter at a *table-dependent*
        address instead of the dense layout's affine ``(slot, pos)``; with
        a donated pool the compiled step still updates B rows in place.
        Dead slots' table rows point at the trash page, so their writes
        are harmless by construction.

        Quantized pools (scale leaves present) quantize each fresh row on
        write — value scatter plus a scalar scale scatter per row — and
        hand the scales to the attention gather for fused dequant.  The
        per-row scale makes the stored bytes a pure function of the row's
        values, so incremental writes and recompute/swap replay produce
        identical pages.  A free slot (table row at the trash page) routes
        nothing through the expert layer.  Returns (logits, cache, stats):
        the expert layers' counts summed over layers
        (``repro.models.moe.MOE_STATS``), None for a model without them;
        the serving engine's paged step reads them here.
        """
        from repro.models.attention import paged_decode_attention
        from repro.serve.kv_cache import TRASH_PAGE, quantize_kv_rows

        x = self._embed(params, tokens[:, None])
        kp, vp, bt = cache["k_pages"], cache["v_pages"], cache["block_tables"]
        quantized = "k_scales" in cache
        ks = cache.get("k_scales")
        vs = cache.get("v_scales")
        page_size = kp.shape[2]
        bidx = jnp.arange(x.shape[0])
        page = bt[bidx, jnp.minimum(pos // page_size, bt.shape[1] - 1)]
        off = pos % page_size

        def write_attend(l, q, k, v):
            nonlocal kp, vp, ks, vs
            if quantized:
                qk, sk = quantize_kv_rows(k[:, 0])
                qv, sv = quantize_kv_rows(v[:, 0])
                kp = kp.at[l, page, off].set(qk.astype(kp.dtype))
                vp = vp.at[l, page, off].set(qv.astype(vp.dtype))
                ks = ks.at[l, page, off].set(sk)
                vs = vs.at[l, page, off].set(sv)
            else:
                kp = kp.at[l, page, off].set(k[:, 0].astype(kp.dtype))
                vp = vp.at[l, page, off].set(v[:, 0].astype(vp.dtype))
            return paged_decode_attention(q, kp, vp, bt, pos, layer=l,
                                          attend_len=attend_len,
                                          k_scales=ks, v_scales=vs,
                                          backend=self.decode_backend)

        logits, stats = self._gqa_decode_loop(
            params, x, pos, write_attend,
            valid=(bt[:, 0] != TRASH_PAGE)[:, None])
        out = {"k_pages": kp, "v_pages": vp, "block_tables": bt}
        if quantized:
            out["k_scales"], out["v_scales"] = ks, vs
        return logits, out, stats

    # ------------------------------------------------------ speculative verify
    def decode_verify_step(self, params, cache, tokens: jnp.ndarray,
                           pos: jnp.ndarray,
                           attend_len: Optional[int] = None,
                           verify_backend: Optional[str] = None):
        """Score a T-token speculative window in one dispatch (paged cache).

        tokens: (B, T) — row b holds [last committed token, draft_1, ...,
        draft_{T-1}] sitting at absolute positions pos[b]..pos[b]+T-1.
        Returns (logits (B, T, V), cache): logits[:, i] is the target
        distribution for the token at position pos+i+1, conditioned on the
        committed prefix plus window tokens 0..i — exactly what T
        sequential ``decode_step`` calls would produce, so greedy
        acceptance (longest matching prefix + one correction token) is
        bit-identical to non-speculative decode.

        T is static (the engine buckets spec_k), so each k compiles one
        executable; T=1 degenerates to single-token decode.  Every window
        token's K/V row is written through the block tables before the
        attention read (rejected rows are rolled back by table edit in the
        allocator, never copied — the next window simply overwrites them).
        """
        if "k_pages" not in cache:
            raise ValueError("decode_verify_step needs a paged cache "
                             "(k_pages/v_pages/block_tables); got leaves "
                             f"{sorted(cache)}")
        x = self._embed(params, tokens)
        x, cache, _ = self._paged_window(params, cache, x, pos, attend_len,
                                         verify_backend)
        logits = self._head(params, x)[..., :self.cfg.vocab]   # (B, T, V)
        return logits, cache

    def _paged_window(self, params, cache, x, pos,
                      attend_len: Optional[int],
                      verify_backend: Optional[str], valid=None):
        """Shared T-token window body over the paged cache: per layer the
        T fresh K/V rows scatter at table-resolved ``(page, offset)``
        pairs, then the verify attention masks each query row at its own
        position.  Backs both the speculative verify
        (:meth:`decode_verify_step`) and the shared-prefix suffix prefill
        (:meth:`prefill_suffix`) — one body keeps their math identical.
        Rows written to the trash page, and rows outside ``valid`` (B, T)
        where given, route nothing through the expert layer.  Returns
        (hidden (B, T, d), new cache, stats)."""
        from repro.models.attention import paged_verify_attention

        from repro.serve.kv_cache import TRASH_PAGE, quantize_kv_rows

        kp, vp, bt = cache["k_pages"], cache["v_pages"], cache["block_tables"]
        quantized = "k_scales" in cache
        ks = cache.get("k_scales")
        vs = cache.get("v_scales")
        page_size = kp.shape[2]
        t = x.shape[1]
        positions = pos[:, None] + jnp.arange(t)[None, :]      # (B, T)
        blk = positions // page_size
        page = jnp.take_along_axis(bt, jnp.minimum(blk, bt.shape[1] - 1),
                                   axis=1)                     # (B, T)
        # a window straddling the end of the pool (pos near max_seq, or a
        # finished slot coasting) must not fold its overflow rows back
        # onto the last live block — those writes go to the trash page
        # (the commit clamp never accepts tokens at such positions)
        page = jnp.where(blk < bt.shape[1], page, TRASH_PAGE)
        off = positions % page_size
        backend = (verify_backend if verify_backend is not None
                   else self.decode_backend)

        def write_attend(l, q, k, v):
            nonlocal kp, vp, ks, vs
            if quantized:
                qk, sk = quantize_kv_rows(k)          # (B,T,H,D) -> (B,T)
                qv, sv = quantize_kv_rows(v)
                kp = kp.at[l, page, off].set(qk.astype(kp.dtype))
                vp = vp.at[l, page, off].set(qv.astype(vp.dtype))
                ks = ks.at[l, page, off].set(sk)
                vs = vs.at[l, page, off].set(sv)
            else:
                kp = kp.at[l, page, off].set(k.astype(kp.dtype))
                vp = vp.at[l, page, off].set(v.astype(vp.dtype))
            return paged_verify_attention(q, kp, vp, bt, pos, layer=l,
                                          attend_len=attend_len,
                                          k_scales=ks, v_scales=vs,
                                          backend=backend)

        routed = page != TRASH_PAGE
        if valid is not None:
            routed = routed & valid
        x, stats = self._gqa_decode_layers(params, x, positions,
                                           write_attend, routed)
        out = {"k_pages": kp, "v_pages": vp, "block_tables": bt}
        if quantized:
            out["k_scales"], out["v_scales"] = ks, vs
        return x, out, stats

    # -------------------------------------------------- shared-prefix prefill
    def prefill_suffix(self, params, cache, tokens: jnp.ndarray,
                       start_pos: jnp.ndarray, last_idx: jnp.ndarray,
                       attend_len: Optional[int] = None,
                       verify_backend: Optional[str] = None):
        """Prefill only the un-cached suffix of a prompt whose prefix
        pages are already mapped (prefix sharing — the cached positions'
        K/V is *someone else's* physical pages, reached through this
        slot's block table).

        tokens: (B, T) right-padded suffix; row b's real tokens sit at
        absolute positions start_pos[b] .. start_pos[b] + last_idx[b],
        with ``last_idx[b]`` the index of the row's last real token
        inside the window.  Returns (logits (B, V) at each row's last
        real token, cache, stats): the expert layers' counts summed over
        layers (``repro.models.moe.MOE_STATS``), None without them.

        This is the verify window re-aimed at admission: every suffix
        K/V row is written through the block tables (shared prefix pages
        are never written — the suffix starts past them by construction,
        see :meth:`PagedCacheManager.plan_admit`), each query row attends
        the cached prefix plus the window causally, and only the compute
        for ``T`` suffix tokens is spent instead of the full prompt.
        Padding rows past ``last_idx`` write into the slot's private tail
        page (masked and progressively overwritten by decode, exactly
        like right-padded dense prefill) or the trash page, and route
        nothing through the expert layer."""
        if "k_pages" not in cache:
            raise ValueError("prefill_suffix needs a paged cache "
                             "(k_pages/v_pages/block_tables); got leaves "
                             f"{sorted(cache)}")
        x = self._embed(params, tokens)
        real = jnp.arange(tokens.shape[1])[None, :] <= last_idx[:, None]
        x, cache, stats = self._paged_window(params, cache, x, start_pos,
                                             attend_len, verify_backend,
                                             real)
        idx = jnp.broadcast_to(last_idx[:, None, None],
                               (x.shape[0], 1, x.shape[2]))
        last = jnp.take_along_axis(x, idx, axis=1)             # (B, 1, d)
        logits = self._head(params, last)[:, 0, :self.cfg.vocab]
        return logits, cache, stats

    # --------------------------------------------------------------- prefill
    def prefill(self, params, batch: Dict[str, jnp.ndarray], max_seq: int,
                last_pos: Optional[jnp.ndarray] = None):
        """Process a full prompt; returns (last_logits (B, V), cache).

        The cache matches :meth:`init_cache` layout with positions [0, S)
        filled — the serving engine continues decoding from pos = S (for the
        vlm family S includes the frontend positions).

        last_pos: optional (B,) per-row index of the last *real* token.
        With right-padded prompt batches (bucketed admission) the causal
        mask makes position ``last_pos[b]`` independent of the padding, so
        the returned logits are exact; the padded tail of the cache is
        masked out (and progressively overwritten) by the decode steps,
        and routes nothing through the expert layer.
        """
        return self._prefill(params, batch, max_seq, last_pos)[:2]

    def _prefill(self, params, batch, max_seq, last_pos):
        """:meth:`prefill` with a third output, the expert layers' counts
        summed over layers (``repro.models.moe.MOE_STATS``), None for a
        model without them; the serving engine's paged admission reads
        them here."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens)

        def last_hidden(h):
            if last_pos is None:
                return h[:, -1:, :]
            idx = jnp.broadcast_to(last_pos[:, None, None],
                                   (h.shape[0], 1, h.shape[2]))
            return jnp.take_along_axis(h, idx, axis=1)

        def pad_seq(a, axis=1):
            n = max_seq - a.shape[axis]
            if n <= 0:
                return a
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, n)
            return jnp.pad(a, widths)

        if cfg.family == "ssm":
            def body(h, p):
                h, st = self._rwkv_block(p, h, None)
                return h, st

            x, cache = jax.lax.scan(body, x, params["layers"])
            return (self._head(params, last_hidden(x))[:, 0, :cfg.vocab],
                    cache, None)

        if cfg.family == "hybrid":
            k = cfg.hybrid_attn_every
            n_groups = cfg.n_layers // k
            layers = jax.tree.map(
                lambda a: a.reshape((n_groups, k) + a.shape[1:]),
                params["layers"])

            def group_body(h, gp):
                sp = params["shared_attn"]
                g = rmsnorm(h, sp["ln1"], cfg.norm_eps, self.wf)
                att, (kk, vv) = gqa_block_kv(sp["attn"], g, cfg, causal=True,
                                             chunk_q=self.chunk_q,
                                             backend=self.attn_backend)
                h = h + att
                g = rmsnorm(h, sp["ln2"], cfg.norm_eps, self.wf)
                h = h + swiglu(g, sp["mlp"]["w_gate"], sp["mlp"]["w_up"],
                               sp["mlp"]["w_down"])

                def inner(hh, p):
                    hh, st = self._mamba_block(p, hh, None)
                    return hh, st

                h, states = jax.lax.scan(inner, h, gp)
                return h, (states, pad_seq(kk), pad_seq(vv))

            x, (states, ks, vs) = jax.lax.scan(group_body, x, layers)
            cache = {
                "conv": states["conv"].reshape(
                    (cfg.n_layers,) + states["conv"].shape[2:]),
                "ssm": states["ssm"].reshape(
                    (cfg.n_layers,) + states["ssm"].shape[2:]),
                "attn_k": ks,
                "attn_v": vs,
            }
            return (self._head(params, last_hidden(x))[:, 0, :cfg.vocab],
                    cache, None)

        if cfg.attn_type == "mla":
            def body(h, p):
                g = rmsnorm(h, p["ln1"], cfg.norm_eps, self.wf)
                att, (lat, kr) = mla_block_kv(p["attn"], g, cfg, causal=True,
                                              chunk_q=self.chunk_q,
                                              backend=self.attn_backend)
                h = h + att
                g = rmsnorm(h, p["ln2"], cfg.norm_eps, self.wf)
                h = h + swiglu(g, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                               p["mlp"]["w_down"])
                return h, (pad_seq(lat), pad_seq(kr))

            x, (lats, ropes) = jax.lax.scan(body, x, params["layers"])
            cache = {"latent": lats, "rope": ropes}
            return (self._head(params, last_hidden(x))[:, 0, :cfg.vocab],
                    cache, None)

        # gqa family (dense / moe / encdec / vlm)
        routed = None
        if cfg.family == "moe" and last_pos is not None:
            routed = (jnp.arange(tokens.shape[1])[None, :]
                      <= last_pos[:, None])
        enc = None
        if cfg.family == "encdec":
            enc = self._scan_encoder(
                params, batch["frontend_embeds"].astype(self.compute_dtype))
        if cfg.family == "vlm":
            fe = batch["frontend_embeds"].astype(self.compute_dtype)
            fe = jnp.einsum("bnd,de->bne", fe,
                            params["vit_proj"].astype(fe.dtype))
            x = jnp.concatenate([fe, x], axis=1)

        def body(h, inp):
            p, l = inp
            g = rmsnorm(h, p["ln1"], cfg.norm_eps, self.wf)
            att, (kk, vv) = gqa_block_kv(p["attn"], g, cfg, causal=True,
                                         chunk_q=self.chunk_q,
                                         backend=self.attn_backend)
            h = h + att
            ys = [pad_seq(kk), pad_seq(vv)]
            if cfg.family == "encdec":
                g = rmsnorm(h, p["ln_cross"], cfg.norm_eps, self.wf)
                ck, cv = encode_cross_kv(p["cross"], enc, cfg)
                h = h + cross_block(p["cross"], g, (ck, cv), cfg,
                                    backend=self.attn_backend)
                ys += [ck, cv]
            g = rmsnorm(h, p["ln2"], cfg.norm_eps, self.wf)
            if cfg.family == "moe":
                # the stacked experts, read at layer l where they live
                y, st = held_experts(params["layers"]["moe"], g, cfg,
                                     layer=l, valid=routed,
                                     backend=self.decode_backend)
                h = h + y
                ys.append(st)
            else:
                h = h + swiglu(g, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                               p["mlp"]["w_down"])
            return h, tuple(ys)

        layer_ids = (jnp.arange(self._n_scan_layers) if cfg.family == "moe"
                     else None)
        x, ys = jax.lax.scan(body, x, (params["layers"], layer_ids))
        cache = {"k": ys[0], "v": ys[1]}
        if cfg.family == "encdec":
            cache["cross_k"], cache["cross_v"] = ys[2], ys[3]
        stats = jnp.sum(ys[2], axis=0) if cfg.family == "moe" else None
        return (self._head(params, last_hidden(x))[:, 0, :cfg.vocab], cache,
                stats)

    def _hybrid_decode(self, params, cache, x, pos,
                       attend_len: Optional[int] = None):
        cfg = self.cfg
        k = cfg.hybrid_attn_every
        n_groups = cfg.n_layers // k
        layers = jax.tree.map(
            lambda a: a.reshape((n_groups, k) + a.shape[1:]), params["layers"])
        mamba_states = {
            "conv": cache["conv"].reshape((n_groups, k) + cache["conv"].shape[1:]),
            "ssm": cache["ssm"].reshape((n_groups, k) + cache["ssm"].shape[1:]),
        }

        def group_body(h, inp):
            gp, st, ck, cv = inp
            g = rmsnorm(h, params["shared_attn"]["ln1"], cfg.norm_eps, self.wf)
            att, new_kv = gqa_decode_block(params["shared_attn"]["attn"], g,
                                           cfg, {"k": ck, "v": cv}, pos,
                                           attend_len=attend_len,
                                           backend=self.decode_backend)
            h = h + att
            g = rmsnorm(h, params["shared_attn"]["ln2"], cfg.norm_eps, self.wf)
            h = h + swiglu(g, params["shared_attn"]["mlp"]["w_gate"],
                           params["shared_attn"]["mlp"]["w_up"],
                           params["shared_attn"]["mlp"]["w_down"])

            def inner(hh, inner_inp):
                p, s = inner_inp
                hh, new_s = self._mamba_block(p, hh, s)
                return hh, new_s

            h, new_states = jax.lax.scan(inner, h, (gp, st))
            return h, (new_states, new_kv["k"], new_kv["v"])

        x, (new_states, new_k, new_v) = jax.lax.scan(
            group_body, x, (layers, mamba_states,
                            cache["attn_k"], cache["attn_v"]))
        new_cache = {
            "conv": new_states["conv"].reshape(cache["conv"].shape),
            "ssm": new_states["ssm"].reshape(cache["ssm"].shape),
            "attn_k": new_k,
            "attn_v": new_v,
        }
        return self._head(params, x)[:, 0, :cfg.vocab], new_cache
