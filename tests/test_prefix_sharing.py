"""Prefix-sharing paged KV cache: radix-index units, refcounted
copy-on-write admission in the manager (incl. OOM and shared-boundary
retract edges), shared == unshared greedy serving across admission
orders / forced preemption / speculative decoding, page-bound
accounting, and the gather_slot shared-resolution debug view."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models.lm import Model
from repro.serve.engine import Request, ServeEngine
from repro.serve.kv_cache import (
    TRASH_PAGE,
    PagedCacheManager,
    gather_slot,
    scatter_prefill,
)
from repro.serve.prefix_index import PrefixIndex

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
          "cache_layout": "paged", "page_size": 8, **kw}
    return ServeEngine(model, params, **kw)


def _shared_reqs(n, prefix_len=16, suf_lo=1, suf_hi=8, max_new=5, seed=3,
                 dup_aligned=True):
    """n requests sharing a common ``prefix_len``-token prefix with short
    random suffixes; optionally one exact page-aligned duplicate (the
    copy-on-write admission case)."""
    cfg, _, _ = _model()
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab, prefix_len).tolist()
    reqs = [Request(uid=i,
                    prompt=prefix + rng.integers(
                        0, cfg.vocab, int(rng.integers(suf_lo, suf_hi))
                    ).tolist(),
                    max_new_tokens=max_new)
            for i in range(n)]
    if dup_aligned:
        reqs.append(Request(uid=n, prompt=list(prefix),
                            max_new_tokens=max_new))
    return reqs


def _serve(engine, reqs):
    return engine.serve(copy.deepcopy(reqs))


def _mgr(num_pages, page_size=4, slots=3, max_seq=32):
    return PagedCacheManager(num_pages, page_size, slots, max_seq,
                             prefix_index=PrefixIndex(page_size))


# ---------------------------------------------------------------------------
# radix index units
# ---------------------------------------------------------------------------

def test_index_match_insert_page_granular():
    ix = PrefixIndex(4)
    assert ix.match([1, 2, 3, 4, 5]) == []
    new = ix.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11])
    assert new == [10, 11] and len(ix) == 2
    assert ix.match([1, 2, 3, 4, 5, 6, 7, 8, 9]) == [10, 11]
    assert ix.match([1, 2, 3, 4, 9, 9, 9, 9]) == [10]  # diverges at page 2
    assert ix.match([1, 2, 3]) == []     # partial pages never match
    assert ix.match([1, 2, 3, 9, 9]) == []
    # re-insert keeps existing nodes and registers only the new depth
    new2 = ix.insert([1, 2, 3, 4, 5, 6, 7, 8, 9, 9, 9, 9], [10, 11, 12])
    assert new2 == [12] and len(ix) == 3
    # a private duplicate of an indexed page (CoW fork) is not registered
    assert ix.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 77]) == []
    assert ix.match([1, 2, 3, 4, 5, 6, 7, 8]) == [10, 11]


def test_index_evict_lru_leaf_cascade():
    ix = PrefixIndex(2)
    ix.insert([1, 1, 2, 2, 3, 3], [5, 6, 7])     # chain 5 -> 6 -> 7
    ix.insert([1, 1, 9, 9], [5, 8])              # branch below 5
    held = {6}                                   # a live slot holds page 6
    can = lambda p: p not in held
    freed = ix.evict_lru(10, can)
    # 7 and 8 are evictable leaves; 6 is pinned, which also blocks its
    # ancestor 5 from the cascade
    assert set(freed) == {7, 8} and len(ix) == 2
    assert ix.evictable(can) == 0
    held.clear()
    assert set(ix.evict_lru(10, can)) == {5, 6}
    assert len(ix) == 0


def test_index_evict_lru_order_and_exclude():
    ix = PrefixIndex(2)
    ix.insert([1, 1], [3])
    ix.insert([2, 2], [4])
    ix.match([1, 1])                             # refresh page 3
    assert ix.evict_lru(1, lambda p: True) == [4]
    # exclude masks pages an admission is about to share
    assert ix.evictable(lambda p: True, exclude={3}) == 0


# ---------------------------------------------------------------------------
# manager: refcounted admission, CoW, OOM, shared-boundary retract
# ---------------------------------------------------------------------------

def test_manager_admit_prefix_shares_pages():
    m = _mgr(num_pages=12)
    prompt = list(range(10))                     # 2 full pages + partial
    plan0 = m.plan_admit(prompt)
    assert plan0.cached_tokens == 0 and plan0.private_blocks == 3
    m.admit_prefix(0, plan0)
    m.register_prefix(0, prompt)
    assert len(m.index) == 2
    # identical prompt: shares both full pages, allocates only the tail
    plan1 = m.plan_admit(list(prompt))
    assert plan1.cached_tokens == 8 and plan1.private_blocks == 1
    assert plan1.shared_pages == [int(m.tables[0, 0]), int(m.tables[0, 1])]
    m.admit_prefix(1, plan1)
    assert m.tables[1, 0] == m.tables[0, 0]
    assert m.tables[1, 1] == m.tables[0, 1]
    assert m.tables[1, 2] != m.tables[0, 2]      # private tails differ
    # physical: 3 + 1; logical slot mappings: 3 + 3 (+2 index refs)
    assert m.allocator.used == 4
    assert m.allocator.logical == 8
    # divergence after one page matches one page
    plan2 = m.plan_admit(prompt[:4] + [99] * 6)
    assert plan2.cached_tokens == 4
    assert plan2.shared_pages == [int(m.tables[0, 0])]


def test_manager_cow_fork_on_aligned_full_match():
    m = _mgr(num_pages=10, slots=2)
    prompt = list(range(8))                      # exactly 2 pages
    m.admit_prefix(0, m.plan_admit(prompt))
    m.register_prefix(0, prompt)
    plan = m.plan_admit(list(prompt))
    # the write frontier lands inside the last matched page: fork it
    assert plan.cow_src == int(m.tables[0, 1])
    assert plan.cached_tokens == len(prompt) - 1
    assert plan.private_blocks == 1 and len(plan.shared_pages) == 1
    m.admit_prefix(1, plan)
    assert plan.cow_dst is not None and plan.cow_dst != plan.cow_src
    assert m.tables[1, 0] == m.tables[0, 0]      # shared
    assert int(m.tables[1, 1]) == plan.cow_dst   # forked, private
    m.allocator.assert_writable(plan.cow_dst)
    with pytest.raises(ValueError, match="shared"):
        m.allocator.assert_writable(int(m.tables[1, 0]))
    # the fork source is pinned (slot 0 + index + pin) until the device
    # copy lands, so eviction can never reclaim it mid-fork
    assert m.allocator.refcount(plan.cow_src) == 3
    m.cow_release(plan)
    assert m.allocator.refcount(plan.cow_src) == 2


def test_manager_cow_fork_under_oom():
    """The fork needs a page; with none free and nothing evictable the
    admission fails atomically — tables and refcounts unchanged."""
    m = _mgr(num_pages=3, slots=2, max_seq=16)   # 2 usable pages
    prompt = list(range(8))
    m.admit_prefix(0, m.plan_admit(prompt))      # takes both pages
    m.register_prefix(0, prompt)
    before = {p: m.allocator.refcount(p) for p in m.owned[0]}
    plan = m.plan_admit(list(prompt))
    assert plan.cow_src is not None
    assert not m.can_admit_plan(plan)
    assert m.admit_prefix(1, plan) is None
    assert not m.owned[1]
    assert all(t == TRASH_PAGE for t in m.tables[1])
    assert {p: m.allocator.refcount(p) for p in m.owned[0]} == before


def test_manager_retract_above_shared_boundary():
    """retract_above must never free a page another slot (or the index)
    holds: retraction into a shared region drops only this slot's refs."""
    m = _mgr(num_pages=12, slots=2)
    prompt = list(range(12))                     # 3 aligned pages
    m.admit_prefix(0, m.plan_admit(prompt))
    m.register_prefix(0, prompt)
    plan = m.plan_admit(list(prompt))            # shares 2, forks 1
    m.admit_prefix(1, plan)
    shared_pg = int(m.tables[1, 1])
    assert shared_pg == int(m.tables[0, 1])
    used_before = m.allocator.used
    n = m.retract_above(1, 4)                    # keep block 0 only
    assert n == 2                                # blocks 1 (shared) + 2 (fork)
    assert m.tables[1, 1] == TRASH_PAGE and m.tables[1, 2] == TRASH_PAGE
    assert int(m.tables[0, 1]) == shared_pg      # other slot untouched
    assert m.allocator.refcount(shared_pg) == 2  # slot 0 + index
    assert m.allocator.used == used_before - 1   # only the fork freed


def test_manager_release_keeps_index_pages_then_eviction_reclaims():
    m = _mgr(num_pages=5, slots=1, max_seq=16)   # 4 usable
    prompt = list(range(8))
    m.admit_prefix(0, m.plan_admit(prompt))
    m.register_prefix(0, prompt)
    m.release(0)
    # the index keeps the released prefix alive as reusable cache
    assert m.allocator.used == 2 and m.allocator.free == 2
    assert len(m.index) == 2
    # and the same prompt later re-admits against it with zero prefill
    plan = m.plan_admit(list(prompt))
    assert plan.cached_tokens == 7
    # an unrelated admission needing the whole pool evicts LRU entries
    plan2 = m.plan_admit([99] * 16)
    assert plan2.private_blocks == 4
    assert m.can_admit_plan(plan2)
    assert m.admit_prefix(0, plan2) is not None
    assert m.evictions == 2 and len(m.index) == 0


# ---------------------------------------------------------------------------
# gather_slot: shared pages resolve, truly-unmapped entries poison
# ---------------------------------------------------------------------------

def test_gather_slot_resolves_shared_and_poisons_unmapped():
    L, H, D, ps, P = 2, 2, 8, 4, 10
    m = PagedCacheManager(P, ps, 2, 16, prefix_index=PrefixIndex(ps))
    prompt = list(range(10))                     # 2 full pages + partial
    m.admit_prefix(0, m.plan_admit(prompt))
    pool = {"k_pages": jnp.zeros((L, P, ps, H, D)),
            "v_pages": jnp.zeros((L, P, ps, H, D))}
    pcache = {
        "k": jax.random.normal(jax.random.PRNGKey(1), (L, 1, 12, H, D)),
        "v": jax.random.normal(jax.random.PRNGKey(2), (L, 1, 12, H, D))}
    # protocol order matters: scatter targets must be private, so the
    # prefill lands before the prefix is published / shared
    pool = scatter_prefill(pool, pcache,
                           jnp.asarray(m.prefill_page_idx(0, 3))[None, :])
    m.register_prefix(0, prompt)
    m.admit_prefix(1, m.plan_admit(list(prompt)))
    v0 = gather_slot(pool, jnp.asarray(m.tables[0]), ps)
    v1 = gather_slot(pool, jnp.asarray(m.tables[1]), ps)
    # the shared prefix resolves identically through both tables
    np.testing.assert_array_equal(np.asarray(v0["k"][:, :8]),
                                  np.asarray(v1["k"][:, :8]))
    np.testing.assert_array_equal(np.asarray(v0["k"][:, :8]),
                                  np.asarray(pcache["k"][:, 0, :8]))
    # mapped-but-stale rows are real data; unmapped blocks poison to NaN
    assert not np.isnan(np.asarray(v0["k"][:, :12])).any()
    assert np.isnan(np.asarray(v0["k"][:, 16:])).all()
    assert np.isnan(np.asarray(v1["v"][:, 16:])).all()


# ---------------------------------------------------------------------------
# engine: shared == unshared, bit-identical
# ---------------------------------------------------------------------------

def test_shared_matches_unshared_greedy():
    reqs = _shared_reqs(4)
    want = _serve(_engine(), reqs)
    eng = _engine(prefix_sharing=True)
    got = _serve(eng, reqs)
    assert got == want
    p = eng.last_pool_stats
    assert p.sharing_ratio > 1.0
    assert p.cached_prefix_tokens > 0
    assert p.cow_forks >= 1                      # the aligned duplicate


def test_shared_matches_unshared_across_admission_orders():
    reqs = _shared_reqs(5, seed=11)
    want = _serve(_engine(batch_slots=3), reqs)
    rng = np.random.default_rng(0)
    for trial in range(3):
        order = list(reqs)
        rng.shuffle(order)
        got = _serve(_engine(batch_slots=3, prefix_sharing=True), order)
        assert got == want, f"trial {trial}"


def test_shared_forced_preemption_matches_unshared():
    """A pool too small for the working set forces preempt-and-requeue;
    prefix sharing must stay bit-identical (and the resumed request
    re-matches its own published prefix)."""
    reqs = [Request(uid=0, prompt=list(range(1, 17)), max_new_tokens=12),
            Request(uid=1, prompt=list(range(1, 17)) + [77, 78],
                    max_new_tokens=12)]
    want = _serve(_engine(), reqs)
    eng = _engine(prefix_sharing=True, num_pages=6)
    got = _serve(eng, reqs)
    assert got == want
    assert eng.preemptions >= 1


def test_shared_temperature_sampling_matches_unshared():
    reqs = _shared_reqs(4, seed=5, max_new=5)
    want = _serve(_engine(temperature=0.7), reqs)
    got = _serve(_engine(temperature=0.7, prefix_sharing=True), reqs)
    assert got == want


def test_shared_with_spec_decode_matches_unshared():
    """Speculative windows ride shared prefixes: rollback retracts only
    private window pages, outputs stay bit-identical."""
    reqs = _shared_reqs(3, seed=7, max_new=6)
    want = _serve(_engine(), reqs)
    got_spec = _serve(_engine(prefix_sharing=True, spec_k=2,
                              draft="self:1"), reqs)
    assert got_spec == want


def test_shared_page_bound():
    """The acceptance bound: N requests over a page-aligned common prefix
    allocate at most prefix_pages + N * suffix_pages physical pages."""
    ps = 8
    n = 4
    reqs = _shared_reqs(n, prefix_len=16, suf_lo=1, suf_hi=8, max_new=5,
                        dup_aligned=False)
    eng_off = _engine(batch_slots=2)
    eng_on = _engine(batch_slots=2, prefix_sharing=True)
    want = _serve(eng_off, reqs)
    got = _serve(eng_on, reqs)
    assert got == want
    prefix_pages = 16 // ps
    suffix_pages = sum(
        -(-(len(r.prompt) + r.max_new_tokens - 1) // ps) - prefix_pages
        for r in reqs)
    p_on, p_off = eng_on.last_pool_stats, eng_off.last_pool_stats
    assert p_on.peak_used_pages <= prefix_pages + suffix_pages
    assert p_on.peak_used_pages < p_off.peak_used_pages
    # every request after the first served its whole prefix from cache
    cached = [eng_on.last_stats[r.uid]["cached_prefix_tokens"]
              for r in reqs]
    assert cached[0] == 0 and all(c == 16 for c in cached[1:])


def test_shared_stats_logical_vs_physical():
    reqs = _shared_reqs(4, seed=9)
    eng = _engine(prefix_sharing=True)
    results = _serve(eng, reqs)
    p = eng.last_pool_stats
    assert p.logical_tokens == p.logical_pages * p.page_size
    assert p.physical_tokens == p.physical_pages * p.page_size
    assert p.peak_logical_pages >= p.peak_used_pages
    assert p.sharing_ratio >= 1.0
    # all slots released: remaining pages are exactly the index cache
    assert p.logical_pages == 0 and p.physical_pages == 0
    assert p.used_pages == p.index_pages > 0
    for uid in results:
        assert "cached_prefix_tokens" in eng.last_stats[uid]


def test_engine_rejects_sharing_misconfiguration():
    cfg, model, params = _model()
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(model, params, max_seq=32, batch_slots=2,
                    prefix_sharing=True)
    # vlm prefills frontend embeddings, which the prompt's ids do not name
    cfg2, model2, params2 = _model("internvl2-1b")
    with pytest.raises(ValueError, match="family"):
        ServeEngine(model2, params2, max_seq=32, batch_slots=2,
                    cache_layout="paged", prefix_sharing=True)


# ---------------------------------------------------------------------------
# property test: sharing on == off over random overlapping schedules
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None)
    @given(data=st.data())
    def test_property_sharing_equals_unshared(data):
        """Random admit/decode/release/preempt schedules with overlapping
        prompts (a small pool of prefixes, random depths and suffixes):
        prefix sharing must be output-invisible."""
        cfg, _, _ = _model()
        rng = np.random.default_rng(
            data.draw(st.integers(0, 2 ** 16), label="seed"))
        base = rng.integers(0, cfg.vocab, 24).tolist()
        n = data.draw(st.integers(3, 6), label="n_requests")
        reqs = []
        for i in range(n):
            depth = data.draw(st.integers(0, 20), label=f"depth{i}")
            extra = data.draw(st.integers(1, 6), label=f"extra{i}")
            mnew = data.draw(st.integers(1, 7), label=f"mnew{i}")
            prompt = base[:depth] + rng.integers(
                0, cfg.vocab, extra).tolist()
            reqs.append(Request(uid=i, prompt=prompt, max_new_tokens=mnew))
        slots = data.draw(st.integers(1, 3), label="slots")
        # pool from barely-fits (forcing preemption + eviction) upward
        longest = max(min(len(r.prompt) + r.max_new_tokens - 1, 48)
                      for r in reqs)
        min_pages = -(-longest // 8)
        num_pages = data.draw(st.integers(min_pages + 1, 19), label="pages")
        want = _serve(_engine(batch_slots=slots, num_pages=num_pages), reqs)
        got = _serve(_engine(batch_slots=slots, num_pages=num_pages,
                             prefix_sharing=True), reqs)
        assert got == want
