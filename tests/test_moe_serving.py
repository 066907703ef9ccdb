"""The MoE serving path against the plain float32 reference of its model
family (``bench/families/qwen2_moe.py``, Qwen2-MoE at small widths, on
seeded random weights made by that file for both).

The program runs in float32 here, so where it computes what the
reference computes the two differ by float32 rounding alone: summation
orders differ (a grouped matmul over sorted rows against every expert
over every row, a blocked softmax against a whole one).  Logit
tolerances are therefore ``TOL`` = 1e-4, about 50x the float32
differences these widths show (~2e-6 on logits of magnitude ~4) and far
below what a bfloat16 run gives (~4e-2): ``test_paged_decode_matches_
reference`` runs the program in bfloat16 and requires it to fail ``TOL``.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.kernels.grouped_matmul.ops import grouped_matmul_op
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref
from repro.models.lm import Model
from repro.models.moe import held_experts, moe_block, shared_expert
from repro.serve.engine import Request, ServeEngine
from repro.serve.kv_cache import scatter_prefill

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.families import qwen2_moe as family  # noqa: E402

SEED = 123
TOL = 1e-4      # float32 rounding, see the module docstring


def _dims(**over):
    dims = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
            "d_head": 16, "d_ff": 32, "shared_ff": 48, "n_experts": 8,
            "experts_held": 4, "expert_offset": 2, "top_k": 2,
            "norm_topk_prob": False, "vocab": 128, "tie_embeddings": False,
            "norm_eps": 1e-6, "rope_theta": 1e6}
    dims.update(over)
    return dims


def _program(dims, dtype=jnp.float32):
    cfg = family.program_config({"name": "tiny-qwen2-moe"}, dims)
    model = Model(cfg, remat=False, param_dtype=jnp.float32,
                  compute_dtype=dtype)
    return cfg, model, family.make_params(dims, SEED, dtype=jnp.float32)


def _layer0(params):
    return jax.tree.map(lambda a: a[0], params["layers"])


def _rows(dims, n=24, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, n, dims["d_model"]))


@pytest.mark.parametrize("sizes,layer", [([10, 0, 50, 3], None),
                                         ([0, 0, 0, 0], None),
                                         ([100, 60, 30, 10], None),
                                         ([10, 0, 50, 3], 2)])
def test_grouped_matmul_kernel_matches_reference(sizes, layer):
    """The Pallas kernel (interpret mode) on the rows its groups own,
    from one layer's experts or from layer 2 of a stack of 3; rows past
    the last group are the caller's to ignore."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    lhs = jax.random.normal(k1, (200, 256), jnp.float32)
    stack = jax.random.normal(k2, (3, 4, 256, 384), jnp.float32)
    rhs = stack[1] if layer is None else stack
    sizes = jnp.asarray(sizes, jnp.int32)
    n = int(sizes.sum())
    got = grouped_matmul_op(lhs, rhs, sizes, layer, interpret=True)[:n]
    want = grouped_matmul_ref(lhs, stack[layer or 1], sizes)[:n]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ------------------------------------------------- (a) the layer itself
@pytest.mark.parametrize("norm", [False, True])
def test_held_layer_matches_reference(norm):
    dims = _dims(norm_topk_prob=norm)
    cfg, _, params = _program(dims)
    p = _layer0(params)["moe"]
    x = _rows(dims)
    got, stats = held_experts(p, x, cfg)
    want = (family.routed(x[0], p, dims, False)
            + family.shared(x[0], p, False))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=TOL)
    # what the layer counts: pairs that land on held experts
    probs = jax.nn.softmax(x[0] @ p["router"], axis=-1)
    top = np.asarray(jax.lax.top_k(probs, dims["top_k"])[1])
    local = top - dims["expert_offset"]
    mine = local[(local >= 0) & (local < dims["experts_held"])]
    per = np.bincount(mine, minlength=dims["experts_held"])
    assert stats.tolist() == [per.sum(), (per > 0).sum(), per.max()]


# ----------------------------------------- (b) the shares add up to it
def test_four_shares_add_up_to_the_uncut_layer():
    """Four chips each hold a quarter of the experts: their routed parts
    plus the shared expert, counted once, give the uncut layer."""
    whole = _dims(experts_held=8, expert_offset=0)
    _, _, params = _program(whole)
    p = _layer0(params)["moe"]
    x = _rows(whole)
    want = family.routed(x[0], p, whole, False) + family.shared(x[0], p,
                                                                False)
    total = shared_expert(p, x)
    for offset in (0, 2, 4, 6):
        share = _dims(experts_held=2, expert_offset=offset)
        cfg, _, sp = _program(dict(share, shared_ff=0))
        part = {n: p[n][offset:offset + 2] for n in ("w_gate", "w_up",
                                                    "w_down")}
        # a share's weights are that slice of the whole layer's
        for n, w in part.items():
            np.testing.assert_array_equal(np.asarray(_layer0(sp)["moe"][n]),
                                          np.asarray(w))
        total = total + held_experts(dict(p, **part), x, cfg)[0]
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=TOL)


def test_dropless_layer_equals_capacity_dispatch_without_drops():
    """Every expert held, no shared expert (olmoe, granite-moe): the
    serving layer computes what the training layer computes when its
    capacity drops nothing."""
    cfg = reduced_config("olmoe-1b-7b")
    model = Model(cfg, remat=False, compute_dtype=jnp.float32)
    p = _layer0(model.init(jax.random.PRNGKey(0)))["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    got, _ = held_experts(p, x, cfg)
    want = moe_block(p, x, cfg, capacity_factor=float(cfg.n_experts))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ----------------------------- (c) prefill, then decode through pages
def _paged_cache(model, slots, max_seq, page_size=8):
    cache = model.init_cache(slots, max_seq, layout="paged",
                             page_size=page_size)
    nb = cache["block_tables"].shape[1]
    # slot s owns pages 1 + s * nb ...; the trash page is 0
    tables = 1 + np.arange(slots * nb, dtype=np.int32).reshape(slots, nb)
    cache["block_tables"] = jnp.asarray(tables)
    return cache


def _paged_decode_gap(dims, dtype):
    """Largest logit difference from the reference over a prompt's
    prefill and the decode steps after it, through the paged cache."""
    _, model, params = _program(dims, dtype)
    toks = np.random.default_rng(1).integers(0, dims["vocab"], 14)
    n_prompt, page_size = 6, 8
    ref = family.logits(dims, SEED, toks)
    cache = _paged_cache(model, 1, 32, page_size)
    logits, pcache = model.prefill(params, {"tokens": jnp.asarray(
        toks[None, :n_prompt])}, n_prompt, jnp.asarray([n_prompt - 1]))
    pool = {n: cache[n] for n in ("k_pages", "v_pages")}
    pool = scatter_prefill(pool, pcache, cache["block_tables"][:, :1])
    cache = dict(cache, **pool)
    gaps = [np.abs(np.asarray(logits[0]) - ref[n_prompt - 1]).max()]
    for t in range(n_prompt, len(toks)):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray(toks[t:t + 1]), jnp.asarray([t]))
        gaps.append(np.abs(np.asarray(logits[0]) - ref[t]).max())
    return max(gaps)


def test_paged_decode_matches_reference():
    dims = _dims()
    assert _paged_decode_gap(dims, jnp.float32) < TOL
    # the tolerance is tight enough that serving in bfloat16 fails it
    assert _paged_decode_gap(dims, jnp.bfloat16) > TOL


# ------------------------- (d) padded batched admission == batch of one
def test_padded_batch_prefill_equals_one_at_a_time():
    dims = _dims()
    _, model, params = _program(dims)
    rng = np.random.default_rng(2)
    lens = [5, 11, 8]
    bucket = 16
    prompts = [rng.integers(0, dims["vocab"], n) for n in lens]
    toks = np.zeros((len(lens), bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    batched, _, stats = model._prefill(
        params, {"tokens": jnp.asarray(toks)}, bucket,
        jnp.asarray([n - 1 for n in lens]))
    alone = [model._prefill(params, {"tokens": jnp.asarray(p[None])}, len(p),
                            None) for p in prompts]
    for i, (logits, _, _) in enumerate(alone):
        np.testing.assert_allclose(np.asarray(batched[i]),
                                   np.asarray(logits[0]), atol=TOL)
    # padding rows route nothing: the batch computes the same pairs
    assert int(stats[0]) == sum(int(s[0]) for _, _, s in alone)


# ------------------------------------- (e) prefix sharing on == off
def _serve(**kw):
    cfg = reduced_config("qwen2-57b-a14b")
    model = Model(cfg, remat=False, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_seq=64, batch_slots=3,
                      cache_layout="paged", page_size=8, prompt_block=8,
                      attend_block=16, **kw)
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab, 17).tolist()
    reqs = [Request(uid=i, prompt=prefix + rng.integers(
        0, cfg.vocab, 3 + i).tolist(), max_new_tokens=5) for i in range(5)]
    return eng.serve(reqs), eng.last_stats["counters"]


def test_prefix_sharing_matches_unshared():
    shared, c_on = _serve(prefix_sharing=True)
    plain, c_off = _serve(prefix_sharing=False)
    assert shared == plain
    # a prefix hit prefills the suffix alone: fewer routed pairs
    assert 0 < c_on["moe_routed_rows"] < c_off["moe_routed_rows"]
    for c in (c_on, c_off):
        # the busiest held expert against the mean of the 4 held
        assert 1 <= c["moe_rows_max"] * 4 / c["moe_routed_rows"] <= 4
        assert c["moe_experts_hit"] > 0
