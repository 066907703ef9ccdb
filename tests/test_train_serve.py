"""Train/serve layer tests: loss math, accumulation, checkpoint restart,
and prefill/decode consistency against the training-time forward pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.models.config import ModelConfig
from repro.models.lm import Model
from repro.optim.optimizer import AdamWConfig
from repro.train.step import (
    chunked_xent_loss,
    init_train_state,
    make_loss_fn,
    make_train_step,
)
from repro.train.trainer import Trainer, TrainerConfig

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab=256, max_seq=64)


def _batch(cfg, b=2, s=16, seed=0):
    data = SyntheticPipeline(DataConfig(
        vocab=cfg.vocab, seq_len=s, global_batch=b, seed=seed,
        n_frontend_tokens=cfg.n_frontend_tokens, d_model=cfg.d_model))
    return data.batch_at(0)


# ---------------------------------------------------------------------------
# Loss math
# ---------------------------------------------------------------------------

def test_chunked_xent_matches_full():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 16, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 128))
    t = jax.random.randint(jax.random.fold_in(key, 2), (2, 16), 0, 128)
    mask = jnp.ones((2, 16)).at[:, -1].set(0.0)
    full = chunked_xent_loss(x, w, t, mask, n_chunks=1)
    chunked = chunked_xent_loss(x, w, t, mask, n_chunks=4)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               rtol=1e-5)


def test_chunked_xent_grads_match():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 8, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 64))
    t = jax.random.randint(jax.random.fold_in(key, 2), (2, 8), 0, 64)
    mask = jnp.ones((2, 8))
    g1 = jax.grad(lambda a, b: chunked_xent_loss(a, b, t, mask, 1),
                  argnums=(0, 1))(x, w)
    g4 = jax.grad(lambda a, b: chunked_xent_loss(a, b, t, mask, 4),
                  argnums=(0, 1))(x, w)
    for a, b in zip(g1, g4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_grad_accumulation_equivalence():
    model = Model(TINY, compute_dtype=jnp.float32)
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    state = init_train_state(model, jax.random.PRNGKey(0))
    batch = _batch(TINY, b=4, s=16)
    s1, m1 = jax.jit(make_train_step(model, opt, vocab_chunks=1))(state, batch)
    s2, m2 = jax.jit(make_train_step(model, opt, vocab_chunks=1,
                                     accum_steps=2))(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    l1 = jax.tree.leaves(s1.params)
    l2 = jax.tree.leaves(s2.params)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_loss_decreases_tiny():
    """Train 25 tiny steps, then compare the loss on a *fixed* batch under
    the initial vs trained params.  (The per-step history compares losses
    of different random batches, whose spread at this batch size is larger
    than 25 steps of progress — a coin flip, not a learning signal.)"""
    model = Model(TINY, compute_dtype=jnp.float32)
    data = SyntheticPipeline(DataConfig(vocab=TINY.vocab, seq_len=32,
                                        global_batch=4, seed=1))
    opt = AdamWConfig(lr=2e-3, warmup_steps=2, total_steps=30)
    tr = Trainer(model, data, opt,
                 TrainerConfig(total_steps=25, vocab_chunks=2))
    state, hist = tr.run(jax.random.PRNGKey(0))
    losses = [m["loss"] for _, m in hist]
    assert np.isfinite(losses).all()

    loss_fn = jax.jit(make_loss_fn(model, vocab_chunks=2))
    init_state = init_train_state(model, jax.random.PRNGKey(0))
    fixed = data.batch_at(0)
    before = float(loss_fn(init_state.params, fixed))
    after = float(loss_fn(state.params, fixed))
    assert after < before - 0.05, (before, after)


def test_checkpoint_restart_exact(tmp_path):
    ckpt = str(tmp_path / "ck")
    model = Model(TINY, compute_dtype=jnp.float32)
    data = SyntheticPipeline(DataConfig(vocab=TINY.vocab, seq_len=16,
                                        global_batch=2, seed=2))
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)

    cfg = TrainerConfig(total_steps=10, checkpoint_every=5,
                        checkpoint_dir=ckpt, vocab_chunks=1)
    tr = Trainer(model, data, opt, cfg)
    _s, hist_full = tr.run(jax.random.PRNGKey(0))

    # fresh trainer resumes at step 10's checkpoint... simulate preemption at
    # step 5 by re-running with total 10 from the step-5 checkpoint dir copy
    # -> simpler: run 5 steps into a new dir, resume to 10, compare losses.
    ckpt2 = str(tmp_path / "ck2")
    tr_a = Trainer(model, data, opt, TrainerConfig(
        total_steps=5, checkpoint_every=5, checkpoint_dir=ckpt2,
        vocab_chunks=1))
    tr_a.run(jax.random.PRNGKey(0))
    tr_b = Trainer(model, data, opt, TrainerConfig(
        total_steps=10, checkpoint_every=5, checkpoint_dir=ckpt2,
        vocab_chunks=1))
    _s2, hist_resumed = tr_b.run(jax.random.PRNGKey(0))
    assert hist_resumed[0][0] == 5  # resumed, not restarted
    np.testing.assert_allclose(
        hist_full[-1][1]["loss"], hist_resumed[-1][1]["loss"], rtol=1e-5)


# ---------------------------------------------------------------------------
# Prefill / decode consistency (every family)
# ---------------------------------------------------------------------------

PREFILL_ARCHS = ["qwen2-1.5b", "minicpm3-4b", "olmoe-1b-7b", "rwkv6-7b",
                 "zamba2-2.7b", "whisper-small", "internvl2-1b"]


@pytest.mark.parametrize("arch", PREFILL_ARCHS)
def test_prefill_decode_matches_forward(arch):
    import dataclasses

    cfg = reduced_config(arch)
    if cfg.family == "moe":
        # training's capacity dispatch drops tokens by length; pin a no-drop
        # capacity so it computes what the dropless prefill/decode compute
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = Model(cfg, compute_dtype=jnp.float32, remat=False)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    b, s_prompt, s_total = 2, 6, 9
    batch = _batch(cfg, b=b, s=s_total, seed=3)
    tokens = batch["tokens"]

    # reference: training-time forward over the full sequence
    ref_logits = model.forward(params, batch)          # (B, S, V)

    # prefill on the prompt prefix
    pre_batch = dict(batch, tokens=tokens[:, :s_prompt])
    max_seq = s_total + cfg.n_frontend_tokens + 2
    logits_p, cache = model.prefill(params, pre_batch, max_seq)
    np.testing.assert_allclose(np.asarray(logits_p),
                               np.asarray(ref_logits[:, s_prompt - 1]),
                               rtol=5e-3, atol=5e-3)

    # decode the remaining tokens one by one
    offset = cfg.n_frontend_tokens if cfg.family == "vlm" else 0
    for t in range(s_prompt, s_total):
        pos = jnp.full((b,), t + offset, jnp.int32)
        logits_d, cache = model.decode_step(params, cache, tokens[:, t], pos)
        np.testing.assert_allclose(
            np.asarray(logits_d), np.asarray(ref_logits[:, t]),
            rtol=5e-3, atol=5e-3,
            err_msg=f"{arch} decode step {t}")


def test_serve_engine_slots():
    from repro.serve.engine import Request, ServeEngine

    cfg = reduced_config("qwen2-1.5b")
    model = Model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_seq=48, batch_slots=2,
                         temperature=0.0, seed=0)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(3, 10))).tolist(),
                    max_new_tokens=int(rng.integers(2, 6)))
            for i in range(5)]
    results = engine.serve(reqs)
    assert set(results) == set(range(5))
    for r in reqs:
        assert len(results[r.uid]) == r.max_new_tokens


def test_generate_greedy_matches_decode_path():
    from repro.serve.engine import ServeEngine

    cfg = reduced_config("qwen2-1.5b")
    model = Model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(1))
    engine = ServeEngine(model, params, max_seq=32, batch_slots=2)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab)
    out = engine.generate(prompts, n_tokens=5)
    assert out.shape == (2, 5)
    assert bool(jnp.all(out >= 0)) and bool(jnp.all(out < cfg.vocab))
