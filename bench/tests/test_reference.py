"""The float32 reference against the program's own float32 forward, at
a reduced size on the CPU: full-prompt prefill logits and paged decode
logits through the cache.  Agreement here means the reference reads the
benchmark's weights in the program's layout and computes the same
architecture (rotary form, biases, tied or untied head)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.families import dense_gqa
from bench.families.dense_gqa import program_config

DIMS = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
        "d_head": 32, "d_ff": 256, "vocab": 512, "tie_embeddings": True,
        "norm_eps": 1e-6, "rope_theta": 1e6}


@pytest.mark.parametrize("tie", [True, False])
def test_prefill_and_paged_decode_match_program(tie):
    from repro.models.lm import Model
    from repro.serve.kv_cache import scatter_prefill

    dims = dict(DIMS, tie_embeddings=tie)
    seed, n, steps, page = 3, 40, 6, 16
    cfg = program_config({"name": "t"}, dims)
    model = Model(cfg, param_dtype=jnp.float32, compute_dtype=jnp.float32,
                  attn_backend="jnp", decode_backend="jnp")
    params = dense_gqa.make_params(dims, seed, jnp.float32)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, dims["vocab"], n + steps).astype(np.int32)
    want = dense_gqa.logits(dims, seed, toks)

    with jax.default_matmul_precision("highest"):
        batch = {"tokens": jnp.asarray(toks[None, :n])}
        last, pc = model.prefill(params, batch, n)
        got = [np.asarray(last[0])]
        max_seq = 64
        pool = model.init_cache(1, max_seq, layout="paged", page_size=page,
                                num_pages=max_seq // page + 1)
        tables = jnp.arange(1, max_seq // page + 1, dtype=jnp.int32)[None]
        pages = scatter_prefill({"k_pages": pool["k_pages"],
                                 "v_pages": pool["v_pages"]},
                                {"k": pc["k"][:, :, :n],
                                 "v": pc["v"][:, :, :n]},
                                tables[:, :-(-n // page)])
        cache = dict(pages, block_tables=tables)
        for i in range(steps - 1):
            pos = jnp.asarray([n + i], jnp.int32)
            lg, cache = model.decode_step(params, cache,
                                          jnp.asarray([toks[n + i]]), pos,
                                          max_seq)
            got.append(np.asarray(lg[0]))
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[n - 1 + i], rtol=0, atol=2e-4)


def test_weights_are_exact_in_bf16():
    p32 = dense_gqa.make_params(DIMS, 5, jnp.float32)
    p16 = dense_gqa.make_params(DIMS, 5, jnp.bfloat16)
    for a, b in zip(jax.tree.leaves(p32), jax.tree.leaves(p16)):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b.astype(jnp.float32)))


def test_stacked_layers_equal_one_layer_at_a_time():
    p = dense_gqa.make_params(DIMS, 2 ** 40 + 9, jnp.float32)
    key = weights.base_key(2 ** 40 + 9)
    one = dense_gqa.layer_leaves(key, DIMS, np.uint32(1), jnp.float32)
    for a, b in zip(jax.tree.leaves(one),
                    jax.tree.leaves(jax.tree.map(lambda x: x[1],
                                                 p["layers"]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_seeds_past_32_bits_differ():
    a = dense_gqa.make_params(DIMS, 7, jnp.float32)["embed"]
    b = dense_gqa.make_params(DIMS, 7 + 2 ** 32, jnp.float32)["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_control_lies_further_from_the_reference():
    """Greedy tokens of the reference read a gap of 0; the float8
    control's first choices read a positive one."""
    rng = np.random.default_rng(1)
    toks = list(rng.integers(0, DIMS["vocab"], 60))
    for _ in range(30):
        toks.append(int(np.argmax(dense_gqa.logits(DIMS, 4, toks)[-1])))
    seqs = [(np.asarray(toks, np.int32), 60)]
    sound = dense_gqa.gaps(DIMS, 4, seqs)[0][0]
    again, ctl = dense_gqa.gaps(DIMS, 4, seqs, control=True)
    ctl = ctl[0]
    np.testing.assert_array_equal(sound, again[0])
    assert sound.shape == ctl.shape == (30,)
    np.testing.assert_allclose(sound, 0, atol=1e-5)
    assert ctl.max() > 1e-3


@pytest.mark.parametrize("tie", [True, False])
def test_blocked_head_matches_whole_head(tie, monkeypatch):
    """A vocabulary walked in blocks that do not divide it (the last
    block overlaps) gives the gaps of one whole block."""
    dims = dict(DIMS, tie_embeddings=tie)
    rng = np.random.default_rng(2)
    seqs = [(rng.integers(0, DIMS["vocab"], 70).astype(np.int32), 50)]
    whole, whole_ctl = dense_gqa.gaps(dims, 6, seqs, control=True)
    monkeypatch.setattr(dense_gqa, "VOCAB_BLOCK", 200)
    dense_gqa._head_gaps.clear_cache()
    part, part_ctl = dense_gqa.gaps(dims, 6, seqs, control=True)
    dense_gqa._head_gaps.clear_cache()
    np.testing.assert_allclose(part[0], whole[0], atol=1e-5)
    np.testing.assert_allclose(part_ctl[0], whole_ctl[0], atol=1e-5)
