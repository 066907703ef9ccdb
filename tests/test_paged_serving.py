"""Paged serving scheduler: paged == dense greedy equivalence across
admission orders and pool pressures (incl. forced preempt-and-requeue),
reproducible temperature>0 sampling, and scheduler observability."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models.lm import Model
from repro.serve.engine import Request, ServeEngine

_CACHE = {}


def _model(arch="qwen2-1.5b"):
    if arch not in _CACHE:
        cfg = reduced_config(arch)
        model = Model(cfg, compute_dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(1))
        _CACHE[arch] = (cfg, model, params)
    return _CACHE[arch]


def _engine(arch="qwen2-1.5b", **kw):
    cfg, model, params = _model(arch)
    kw = {"max_seq": 48, "batch_slots": 2, "temperature": 0.0, "seed": 0,
          **kw}
    return ServeEngine(model, params, **kw)


def _reqs(n, seed=3, plo=3, phi=12, mlo=2, mhi=7, arch="qwen2-1.5b"):
    cfg, _, _ = _model(arch)
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(plo, phi))).tolist(),
                    max_new_tokens=int(rng.integers(mlo, mhi)))
            for i in range(n)]


def _serve(engine, reqs):
    return engine.serve(copy.deepcopy(reqs))


# ---------------------------------------------------------------------------
# greedy equivalence: paged == dense, any admission order / pool size
# ---------------------------------------------------------------------------

def test_paged_matches_dense_greedy():
    reqs = _reqs(6)
    want = _serve(_engine(), reqs)
    got = _serve(_engine(cache_layout="paged", page_size=8), reqs)
    assert got == want


def test_paged_matches_dense_across_admission_orders():
    """Admission order must not change any request's output: greedy
    per-request continuations depend only on (params, prompt)."""
    reqs = _reqs(6, seed=11)
    want = _serve(_engine(), reqs)
    rng = np.random.default_rng(0)
    for trial in range(3):
        order = list(reqs)
        rng.shuffle(order)
        got = _serve(_engine(cache_layout="paged", page_size=8,
                             batch_slots=2 + trial), order)
        assert got == want, f"trial {trial}"


def test_paged_forced_preempt_matches_dense():
    """A pool too small for two growing sequences forces
    preempt-and-requeue; outputs must still be bit-identical to dense."""
    reqs = [Request(uid=0, prompt=list(range(1, 9)), max_new_tokens=12),
            Request(uid=1, prompt=list(range(9, 17)), max_new_tokens=12)]
    want = _serve(_engine(), reqs)
    eng = _engine(cache_layout="paged", page_size=8, num_pages=4)
    prompts_before = [list(r.prompt) for r in reqs]
    got = eng.serve(reqs)
    assert got == want
    assert eng.preemptions >= 1
    assert sum(s["preemptions"] for u, s in eng.last_stats.items()
               if isinstance(u, int)) == eng.preemptions
    # preemption resumes on a copy: caller-owned Requests keep their prompt
    assert [list(r.prompt) for r in reqs] == prompts_before


def test_paged_late_preempt_resume_fits_gate():
    """A request preempted after generating many tokens resumes with those
    tokens folded into its prompt; the worst-case admission gate must
    charge only the *remaining* budget, or a request that always fit
    would be rejected on resume."""
    reqs = [Request(uid=0, prompt=list(range(1, 9)), max_new_tokens=40),
            Request(uid=1, prompt=list(range(9, 17)), max_new_tokens=40)]
    want = _serve(_engine(max_seq=64), reqs)
    eng = _engine(max_seq=64, cache_layout="paged", page_size=8,
                  num_pages=9)
    got = _serve(eng, reqs)
    assert got == want
    assert eng.preemptions >= 1


def test_paged_mixed_lengths_exact_budgets():
    """Mixed prompt/max_new through page-gated admission still honor
    max_new_tokens exactly (including 1-token budgets that complete at
    admission)."""
    reqs = _reqs(7, seed=7, plo=2, phi=20, mlo=1, mhi=8)
    reqs.append(Request(uid=7, prompt=[1, 2, 3], max_new_tokens=1))
    eng = _engine(max_seq=64, batch_slots=3, cache_layout="paged",
                  page_size=8, num_pages=10)
    results = _serve(eng, reqs)
    want = {r.uid: r.max_new_tokens for r in reqs}
    assert set(results) == set(want)
    for uid, toks in results.items():
        assert len(toks) == want[uid]


def test_paged_request_too_large_raises_before_serving():
    """Validation is up-front: an infeasible request anywhere in the queue
    fails the call before any other request is served (no lost results)."""
    eng = _engine(cache_layout="paged", page_size=8, num_pages=3)
    with pytest.raises(ValueError, match="never fit"):
        eng.serve([Request(uid=0, prompt=list(range(30)),
                           max_new_tokens=10)])
    eng2 = _engine(cache_layout="paged", page_size=8, num_pages=6)
    with pytest.raises(ValueError, match="never fit"):
        eng2.serve([Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4),
                    Request(uid=1, prompt=list(range(30)),
                            max_new_tokens=40)])
    assert eng2.preemptions == 0   # nothing ran
    # a prompt with no decode room would spin in the admission gate
    # forever (it can never be granted max_seq-worth of pages): reject it
    eng3 = _engine(max_seq=32, cache_layout="paged", page_size=8,
                   num_pages=9)
    with pytest.raises(ValueError, match="decode room"):
        eng3.serve([Request(uid=0, prompt=list(range(40)),
                            max_new_tokens=2)])


def test_reserving_same_request_objects_is_fresh():
    """serve() must reset per-request state: re-serving the same Request
    objects yields the same outputs and never overruns max_new_tokens or
    mutates the previous call's returned lists."""
    reqs = _reqs(4, seed=13)
    for layout_kw in ({}, {"cache_layout": "paged", "page_size": 8}):
        eng = _engine(**layout_kw)
        first = eng.serve(reqs)
        first_copy = {u: list(v) for u, v in first.items()}
        second = eng.serve(reqs)        # same objects, no reset by caller
        assert second == first_copy
        assert first == first_copy      # first call's lists untouched
        for r in reqs:
            assert len(second[r.uid]) == r.max_new_tokens


def test_paged_moe_family_sequential_admission():
    """MoE admits through padded batched prefill (the dropless expert
    layer routes no padding row) and serves through the paged pool
    exactly as through the dense layout."""
    reqs = _reqs(4, seed=5, arch="olmoe-1b-7b")
    want = _serve(_engine(arch="olmoe-1b-7b"), reqs)
    got = _serve(_engine(arch="olmoe-1b-7b", cache_layout="paged",
                         page_size=8), reqs)
    assert got == want


def test_paged_rejects_stateful_family_and_unfused():
    cfg, model, params = _model("rwkv6-7b")
    with pytest.raises(ValueError):
        ServeEngine(model, params, max_seq=32, batch_slots=2,
                    cache_layout="paged")
    with pytest.raises(ValueError):
        _engine(cache_layout="paged", fused=False)


# ---------------------------------------------------------------------------
# sampling: (uid, position) keys — admission-order independent
# ---------------------------------------------------------------------------

def test_sampling_reproducible_across_admission_orders():
    reqs = _reqs(6, seed=5, mlo=5, mhi=6)
    want = _serve(_engine(temperature=0.7), reqs)
    # shuffled queue + different slot count: same per-uid outputs
    got = _serve(_engine(temperature=0.7, batch_slots=3),
                 list(reversed(reqs)))
    assert got == want
    # paged layout and even preemption keep the same keys
    got_paged = _serve(_engine(temperature=0.7, cache_layout="paged",
                               page_size=8, num_pages=5), reqs)
    assert got_paged == want


def test_sampling_differs_across_uids_and_seeds():
    """Sanity: keys really vary by uid and seed (not all-greedy)."""
    prompt = [5, 6, 7, 8]
    reqs = [Request(uid=i, prompt=list(prompt), max_new_tokens=8)
            for i in range(4)]
    out = _serve(_engine(temperature=1.0), reqs)
    assert len({tuple(v) for v in out.values()}) > 1
    out2 = _serve(_engine(temperature=1.0, seed=123), reqs)
    assert any(out[u] != out2[u] for u in out)


# ---------------------------------------------------------------------------
# observability: latency stats + pool accounting
# ---------------------------------------------------------------------------

def test_last_stats_populated():
    eng = _engine(cache_layout="paged", page_size=8)
    reqs = _reqs(4, seed=9)
    results = _serve(eng, reqs)
    per_req = {u: s for u, s in eng.last_stats.items() if isinstance(u, int)}
    assert set(per_req) == set(results)
    assert eng.last_stats["stragglers"] == []   # lifecycle key, always there
    for uid, s in per_req.items():
        assert s["admit_to_first_s"] >= 0.0
        assert s["finished_s"] >= s["first_token_s"]
        assert s["tokens"] == len(results[uid])
        # steady-state decode rate and e2e rate are separate: tok_s covers
        # only the decode interval (admit->first-token is its own field)
        assert s["tok_s"] > 0.0
        assert s["e2e_tok_s"] > 0.0
        # e2e pays the admit->first-token wall-clock that tok_s excludes
        decode_wall = (s["tokens"] - 1) / s["tok_s"]
        e2e_wall = s["tokens"] / s["e2e_tok_s"]
        assert abs(e2e_wall - (s["admit_to_first_s"] + decode_wall)) < 1e-6
    p = eng.last_pool_stats
    assert p.used_pages == 0            # everything released at the end
    assert p.allocs == p.frees > 0
    assert 0.0 < p.peak_utilization <= 1.0
    # utilization high-water marks
    assert p.peak_tokens == p.peak_used_pages * p.page_size
    assert p.retracts == 0              # no speculation in this engine


def test_one_token_request_has_zero_steady_rate():
    """A request whose budget is exhausted by the admission sample has no
    decode interval: steady tok_s is 0, e2e_tok_s still positive."""
    eng = _engine(cache_layout="paged", page_size=8)
    eng.serve([Request(uid=0, prompt=[1, 2, 3], max_new_tokens=1)])
    s = eng.last_stats[0]
    assert s["tokens"] == 1
    assert s["tok_s"] == 0.0
    assert s["e2e_tok_s"] > 0.0


# ---------------------------------------------------------------------------
# property test: paged == dense over random schedules (hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:       # the deterministic tests above still run
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(data=st.data())
    def test_property_paged_equals_dense(data):
        cfg, _, _ = _model()
        n = data.draw(st.integers(3, 6), label="n_requests")
        rng_seed = data.draw(st.integers(0, 2 ** 16), label="prompt_seed")
        rng = np.random.default_rng(rng_seed)
        reqs = []
        for i in range(n):
            plen = data.draw(st.integers(1, 18), label=f"plen{i}")
            mnew = data.draw(st.integers(1, 9), label=f"mnew{i}")
            reqs.append(Request(
                uid=i, prompt=rng.integers(0, cfg.vocab, plen).tolist(),
                max_new_tokens=mnew))
        order = data.draw(st.permutations(list(range(n))), label="order")
        slots = data.draw(st.integers(1, 3), label="slots")
        # pool from barely-fits (forcing preemption) up to dense parity
        longest = max(min(len(r.prompt) + r.max_new_tokens - 1, 48)
                      for r in reqs)
        min_pages = -(-longest // 8)
        num_pages = data.draw(st.integers(min_pages + 1, 19), label="pages")
        want = _serve(_engine(batch_slots=slots), reqs)
        got = _serve(_engine(batch_slots=slots, cache_layout="paged",
                             page_size=8, num_pages=num_pages),
                     [reqs[i] for i in order])
        assert got == want
