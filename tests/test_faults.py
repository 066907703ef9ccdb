"""Fault-tolerant serving: lifecycle statuses, deterministic injection,
invariant audit, recovery, and graceful degradation.

The load-bearing property everything here leans on: engine outputs are a
pure function of (params, prompt, uid, temperature) — admission order,
slot assignment, preemption, retry, and backend all cancel out.  So a
faulted serve must return bit-identical tokens for every request that
still finishes OK, and the audit sweep must come back clean whatever the
schedule did to the allocator."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models.lm import Model
from repro.serve import (
    STATUS_CANCELLED,
    STATUS_FAILED,
    STATUS_OK,
    STATUS_SHED,
    STATUS_TIMEOUT,
    TERMINAL_STATUSES,
    AuditError,
    Fault,
    FaultSchedule,
    InjectedFault,
    PageAllocator,
    PagedCacheManager,
    Request,
    ServeEngine,
)
from repro.serve.audit import audit_manager

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hyp_st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

_CACHE = {}


def _model(arch="qwen2-1.5b"):
    if arch not in _CACHE:
        cfg = reduced_config(arch)
        model = Model(cfg, compute_dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(1))
        _CACHE[arch] = (cfg, model, params)
    return _CACHE[arch]


def _engine(**kw):
    cfg, model, params = _model()
    kw = {"max_seq": 48, "batch_slots": 2, "temperature": 0.0, "seed": 0,
          "cache_layout": "paged", "page_size": 8, **kw}
    return ServeEngine(model, params, **kw)


def _reqs(n, seed=3, plo=3, phi=12, mlo=2, mhi=7, **fields):
    cfg, _, _ = _model()
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(plo, phi))).tolist(),
                    max_new_tokens=int(rng.integers(mlo, mhi)), **fields)
            for i in range(n)]


def _grow_reqs(n, max_new=8, **fields):
    """6-token prompts on an 8-token page: with admission at round 0, the
    first growth allocation lands at round 2 exactly (positions 6 and 7
    fill the prompt's page, position 8 opens block 1) — what lets the
    hard-OOM tests pin their injection to a round that provably
    allocates."""
    cfg, _, _ = _model()
    return [Request(uid=i,
                    prompt=[(i * 7 + j) % cfg.vocab for j in range(6)],
                    max_new_tokens=max_new, **fields)
            for i in range(n)]


def _statuses(eng):
    return {u: s["status"] for u, s in eng.last_stats.items()
            if isinstance(u, int)}


def _assert_clean(eng):
    p = eng.last_pool_stats
    assert p is not None and p.audit_ok, p.audit_errors
    assert p.used_pages == 0


# ---------------------------------------------------------------------------
# FaultSchedule: deterministic, replayable
# ---------------------------------------------------------------------------

def test_fault_schedule_deterministic():
    a = FaultSchedule.random(7, uids=(0, 1, 2))
    b = FaultSchedule.random(7, uids=(0, 1, 2))
    assert a.faults == b.faults
    assert FaultSchedule.random(8, uids=(0, 1, 2)).faults != a.faults


def test_fault_validation():
    with pytest.raises(ValueError):
        Fault("not-a-kind", step=0)
    with pytest.raises(ValueError):
        Fault("nan", step=-1)
    f = Fault("nan", step=3, span=2)
    assert not f.active_at(2) and f.active_at(3) and f.active_at(4) \
        and not f.active_at(5)


def test_corruption_target_seeded():
    fs = FaultSchedule([Fault("page_corruption", step=1)], seed=4)
    f = fs.faults[0]
    pick = fs.corruption_target(f, 1, [5, 9, 2])
    assert pick == fs.corruption_target(f, 1, [9, 2, 5])  # order-free
    assert pick in (2, 5, 9)
    assert fs.corruption_target(f, 1, []) is None
    assert fs.corruption_target(Fault("page_corruption", step=1, page=7),
                                1, [1, 2]) == 7


# ---------------------------------------------------------------------------
# status taxonomy: shed / timeout / cancel
# ---------------------------------------------------------------------------

def test_shed_reject_newest():
    reqs = _reqs(6)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    eng2 = _engine(max_queue=4, shed_policy="reject-newest")
    out = eng2.serve(copy.deepcopy(reqs))
    stt = _statuses(eng2)
    assert [stt[u] for u in (4, 5)] == [STATUS_SHED] * 2
    assert all(stt[u] == STATUS_OK for u in (0, 1, 2, 3))
    assert out == {u: base[u] for u in (0, 1, 2, 3)}
    assert "queue overflow" in eng2.last_stats[5]["reason"]
    _assert_clean(eng2)


def test_shed_reject_largest():
    reqs = _reqs(6)
    eng = _engine(max_queue=4, shed_policy="reject-largest")
    eng.serve(copy.deepcopy(reqs))
    stt = _statuses(eng)
    sizes = {r.uid: len(r.prompt) + r.max_new_tokens for r in reqs}
    shed = {u for u, v in stt.items() if v == STATUS_SHED}
    assert len(shed) == 2
    kept = set(stt) - shed
    assert max(sizes[u] for u in kept) <= min(sizes[u] for u in shed)


def test_shed_policy_validated():
    with pytest.raises(ValueError):
        _engine(shed_policy="nope")
    with pytest.raises(ValueError):
        _engine(max_queue=0)


def test_cancel_queued_and_live():
    reqs = _reqs(6, mlo=6, mhi=10)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    # cancel one late-queued request before serving, one live mid-flight
    eng.cancel(5)
    fs = FaultSchedule([Fault("cancel", step=2, uid=0)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    stt = _statuses(eng)
    assert stt[5] == STATUS_CANCELLED and stt[0] == STATUS_CANCELLED
    assert 0 not in out and 5 not in out
    for u, toks in out.items():
        assert toks == base[u]
    assert not eng._cancel_uids        # consumed
    _assert_clean(eng)


def test_forced_deadline_timeout():
    reqs = _reqs(4, mlo=6, mhi=10)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    fs = FaultSchedule([Fault("deadline", step=3, uid=1)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    stt = _statuses(eng)
    assert stt[1] == STATUS_TIMEOUT
    assert eng.last_stats[1]["reason"] == "deadline"
    for u, toks in out.items():
        assert toks == base[u]
    _assert_clean(eng)


def test_wall_clock_deadline():
    # a deadline that has already passed expires at the first round
    reqs = _reqs(3)
    reqs[1].deadline_ms = 0.0
    eng = _engine()
    out = eng.serve(reqs)
    stt = _statuses(eng)
    assert stt[1] == STATUS_TIMEOUT and 1 not in out
    assert stt[0] == stt[2] == STATUS_OK


def test_ttft_deadline():
    # far-future TTFT deadlines never fire; an already-expired one kills
    # the request before it is ever admitted
    reqs = _reqs(3, ttft_deadline_ms=1e9)
    eng = _engine()
    eng.serve(reqs)
    assert set(_statuses(eng).values()) == {STATUS_OK}
    reqs2 = _reqs(3)
    reqs2[2].ttft_deadline_ms = 0.0
    out2 = eng.serve(reqs2)
    assert _statuses(eng)[2] == STATUS_TIMEOUT and 2 not in out2
    assert eng.last_stats[2]["reason"] == "ttft_deadline"


def test_duplicate_uid_rejected():
    eng = _engine()
    with pytest.raises(ValueError, match="duplicate"):
        eng.serve([Request(uid=1, prompt=[1, 2], max_new_tokens=2),
                   Request(uid=1, prompt=[3, 4], max_new_tokens=2)])


# ---------------------------------------------------------------------------
# NaN quarantine: only the targeted request fails
# ---------------------------------------------------------------------------

def test_nan_quarantines_only_target():
    reqs = _reqs(6, mlo=6, mhi=10)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    fs = FaultSchedule([Fault("nan", step=1, uid=0, span=2)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    stt = _statuses(eng)
    assert stt[0] == STATUS_FAILED
    assert eng.last_stats[0]["reason"] == "nan-logits"
    assert all(v == STATUS_OK for u, v in stt.items() if u != 0)
    assert 0 not in out
    for u, toks in out.items():
        assert toks == base[u]            # batchmates bit-identical
    _assert_clean(eng)


def test_nan_untargeted_fails_all_live():
    reqs = _reqs(4, mlo=6, mhi=10)
    eng = _engine()
    # wide window, no uid: every request dies at its first decode step
    fs = FaultSchedule([Fault("nan", step=0, span=64)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    stt = _statuses(eng)
    assert not out
    assert all(v == STATUS_FAILED for v in stt.values())
    _assert_clean(eng)


def test_page_corruption_surfaces_as_quarantine():
    reqs = _reqs(4, mlo=6, mhi=10)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    fs = FaultSchedule([Fault("page_corruption", step=2)], seed=11)
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    stt = _statuses(eng)
    assert STATUS_FAILED in stt.values()  # someone read the poisoned page
    for u, toks in out.items():
        assert toks == base[u]
    _assert_clean(eng)


# ---------------------------------------------------------------------------
# exception safety: mid-step failures leave no slot or page held
# ---------------------------------------------------------------------------

def test_fatal_oom_aborts_audit_clean():
    reqs = _grow_reqs(4)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    fs = FaultSchedule([Fault("oom", step=2, raise_exc=True, fatal=True)])
    with pytest.raises(InjectedFault):
        eng.serve(copy.deepcopy(reqs), faults=fs)
    stt = _statuses(eng)
    assert all(v in TERMINAL_STATUSES for v in stt.values())
    assert STATUS_FAILED in stt.values()
    _assert_clean(eng)                    # all pages released on the way out
    # the engine is reusable: the very next serve() is fault-free-correct
    assert eng.serve(copy.deepcopy(reqs)) == base


def test_fatal_kernel_exception_aborts_audit_clean():
    reqs = _reqs(4, mlo=6, mhi=10)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    fs = FaultSchedule([Fault("kernel", step=1, fatal=True)])
    with pytest.raises(InjectedFault):
        eng.serve(copy.deepcopy(reqs), faults=fs)
    _assert_clean(eng)
    assert eng.serve(copy.deepcopy(reqs)) == base


# ---------------------------------------------------------------------------
# recovery: step restart, capped retries, kernel -> SW degradation
# ---------------------------------------------------------------------------

def test_hard_oom_recovers_bit_identical():
    reqs = _grow_reqs(5)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    fs = FaultSchedule([Fault("oom", step=2, raise_exc=True)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    assert eng.recoveries == 1
    assert out == base                    # replay is exact
    assert all(v == STATUS_OK for v in _statuses(eng).values())
    retried = sum(s["retries"] for u, s in eng.last_stats.items()
                  if isinstance(u, int))
    assert retried >= 1                   # someone paid a retry
    _assert_clean(eng)


def test_retry_budget_exhausts_to_failed():
    reqs = _grow_reqs(3, max_retries=0)
    eng = _engine(max_recoveries=4)
    fs = FaultSchedule([Fault("oom", step=2, raise_exc=True)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    stt = _statuses(eng)
    # the two live rows had no retry budget; the queued one rode through
    assert stt[0] == stt[1] == STATUS_FAILED
    assert stt[2] == STATUS_OK and 2 in out
    assert "retries exhausted" in eng.last_stats[0]["reason"]
    _assert_clean(eng)


def test_max_recoveries_cap_propagates():
    reqs = _grow_reqs(3)
    eng = _engine(max_recoveries=1)
    # round 2: growth alloc raises -> recovery #1; round 3: re-admission
    # alloc raises again -> over the cap, escapes
    fs = FaultSchedule([Fault("oom", step=2, raise_exc=True),
                        Fault("oom", step=3, raise_exc=True)])
    with pytest.raises(InjectedFault):
        eng.serve(copy.deepcopy(reqs), faults=fs)
    assert eng.recoveries == 1            # second strike escaped
    _assert_clean(eng)


def test_double_recovery_no_double_fold():
    """Back-to-back recoveries re-requeue already-resumed requests: the
    second fold must absorb only the tokens generated since the first
    (folding the whole accumulator again would duplicate the earlier
    tokens in the resumed prompt and silently corrupt the replay)."""
    reqs = _reqs(4, seed=4, plo=4, phi=10, mlo=8, mhi=9)
    eng = _engine(max_seq=64, max_recoveries=8)
    base = eng.serve(copy.deepcopy(reqs))
    fs = FaultSchedule([Fault("kernel", step=12),
                        Fault("kernel", step=13, span=3)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    assert eng.recoveries >= 2            # the same requests resumed twice
    assert out == base
    assert all(v == STATUS_OK for v in _statuses(eng).values())
    _assert_clean(eng)


def test_kernel_fault_degrades_to_sw():
    reqs = _reqs(5, mlo=6, mhi=10)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    assert not eng.backend_degraded
    fs = FaultSchedule([Fault("kernel", step=2)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    assert eng.backend_degraded
    assert eng.model.decode_backend == "jnp"
    assert eng.verify_backend == "jnp"
    assert out == base                    # HW/SW parity after the fallback
    assert all(v == STATUS_OK for v in _statuses(eng).values())
    _assert_clean(eng)


@pytest.mark.parametrize("pipeline", [True, False])
def test_real_step_exception_escapes_without_degrading(pipeline):
    """A plain exception from a real dispatch (a refused kernel, a device
    fault) is not the injected kernel fault: it escapes the session with
    no step restart and no switch to the jnp lowering."""
    reqs = _reqs(4, mlo=6, mhi=10)
    eng = _engine(pipeline=pipeline)
    base = eng.serve(copy.deepcopy(reqs))
    real_step = eng._paged_step
    calls = []

    def failing_step(*args):
        calls.append(None)
        if len(calls) == 3:
            raise RuntimeError("device fault")
        return real_step(*args)

    eng._paged_step = failing_step
    with pytest.raises(RuntimeError, match="device fault"):
        eng.serve(copy.deepcopy(reqs))
    assert not eng.backend_degraded
    assert eng.recoveries == 0
    assert eng.model.decode_backend is None     # auto lowering kept
    assert STATUS_FAILED in _statuses(eng).values()
    _assert_clean(eng)
    eng._paged_step = real_step
    assert eng.serve(copy.deepcopy(reqs)) == base


def test_soft_oom_blocks_then_drains():
    """A soft-OOM window denies admission/growth without raising; the
    engine preempts or waits it out and finishes bit-identically."""
    reqs = _reqs(5, mlo=6, mhi=10)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    fs = FaultSchedule([Fault("oom", step=0, span=3)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    assert out == base
    assert eng.recoveries == 0            # soft denial never raises
    _assert_clean(eng)


def test_mid_flight_soft_oom_preempts_and_resumes():
    reqs = _grow_reqs(2, max_new=10)
    eng = _engine()
    base = eng.serve(copy.deepcopy(reqs))
    # growth denied at round 2: the newest live request is preempted and
    # requeued; outputs must survive bit-for-bit
    fs = FaultSchedule([Fault("oom", step=2, span=2)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    assert out == base
    assert eng.preemptions >= 1
    _assert_clean(eng)


# ---------------------------------------------------------------------------
# straggler watchdog
# ---------------------------------------------------------------------------

def test_straggler_watchdog_records_event():
    reqs = _reqs(2, mlo=20, mhi=24)       # enough steps to build a median
    eng = _engine(max_seq=64, straggler_factor=3.0)
    fs = FaultSchedule([Fault("straggler", step=12, sleep_s=1.0)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    events = eng.last_stats["stragglers"]
    assert len(events) >= 1
    ev = events[0]
    assert ev["duration_s"] > 3.0 * ev["median_s"]
    assert ev["live_slots"] >= 1
    assert all(v == STATUS_OK for v in _statuses(eng).values())
    assert all(len(t) for t in out.values())


def test_stragglers_key_always_present():
    eng = _engine()
    eng.serve(_reqs(2))
    assert eng.last_stats["stragglers"] == []


# ---------------------------------------------------------------------------
# speculative acceptance collapse -> auto-disable -> cooldown re-enable
# ---------------------------------------------------------------------------

def test_spec_collapse_auto_disables_and_recovers():
    reqs = _reqs(2, seed=5, mlo=30, mhi=34)
    # damp the layer stack so the self-draft tracks the target (as in
    # benchmarks/spec_decode.py): with random-init weights acceptance
    # collapses *naturally* and the governor would fire without a fault
    cfg, model, params = _model()
    params = dict(params, layers=jax.tree.map(lambda a: a * 0.05,
                                              params["layers"]))
    eng = ServeEngine(model, params, max_seq=96, batch_slots=2,
                      temperature=0.0, seed=0, cache_layout="paged",
                      page_size=8, spec_k=4, draft="self:2",
                      spec_disable_window=4, spec_cooldown=4)
    base = eng.serve(copy.deepcopy(reqs))
    assert eng.last_stats[0].get("spec_auto_disables", 0) == 0
    fs = FaultSchedule([Fault("spec_collapse", step=0, uid=0, span=6)])
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    s = eng.last_stats[0]
    assert s.get("spec_auto_disables", 0) >= 1
    # collapse perturbs only *proposals*: committed values never change
    assert out == base
    assert all(v == STATUS_OK for v in _statuses(eng).values())
    # disabled state is per-serve: a fresh call has it re-armed
    out2 = eng.serve(copy.deepcopy(reqs))
    assert out2 == base
    assert eng.last_stats[0].get("spec_auto_disables", 0) == 0
    _assert_clean(eng)


# ---------------------------------------------------------------------------
# audit: constructed violations are detected
# ---------------------------------------------------------------------------

def test_audit_detects_leaked_refcount():
    mgr = PagedCacheManager(num_pages=8, page_size=4, slots=2, max_seq=16)
    mgr.admit(0, 6)
    assert mgr.audit().ok
    # leak: bump a refcount with no holder to account for it
    page = mgr.owned[0][0]
    mgr.allocator._refs[page] += 1
    mgr.allocator._logical += 1
    rep = mgr.audit()
    assert not rep.ok and rep.refcount_mismatches == 1
    with pytest.raises(AuditError):
        rep.raise_if_failed()


def test_audit_detects_orphan_page():
    mgr = PagedCacheManager(num_pages=8, page_size=4, slots=2, max_seq=16)
    mgr.admit(0, 6)
    # orphan: the table forgets a page the allocator still holds
    page = mgr.owned[0].pop()
    mgr.tables[0, 1] = 0
    rep = mgr.audit()
    assert not rep.ok and rep.orphan_pages == 1
    assert any(f"orphan page {page}" in e for e in rep.errors)


def test_audit_detects_free_list_corruption():
    alloc = PageAllocator(8)
    pages = alloc.alloc(2)
    alloc._free.append(pages[0])          # page both free and allocated
    errs = alloc.audit()
    assert any("both free and allocated" in e for e in errs)


def test_audit_detects_double_mapping():
    mgr = PagedCacheManager(num_pages=8, page_size=4, slots=2, max_seq=16)
    mgr.admit(0, 8)
    mgr.tables[0, 1] = mgr.tables[0, 0]   # one page at two logical blocks
    rep = mgr.audit()
    assert not rep.ok
    assert any("two logical blocks" in e for e in rep.errors)


def test_engine_audit_flag_catches_corruption(monkeypatch):
    """audit=True sweeps every round: a deliberately broken release is
    caught at the step that caused it, as AuditError (never recovered)."""
    reqs = _reqs(3, mlo=4, mhi=7)
    eng = _engine(audit=True)
    eng.serve(copy.deepcopy(reqs))        # clean run under per-round audit
    assert all(v == STATUS_OK for v in _statuses(eng).values())

    real_release = PagedCacheManager.release

    def leaky_release(self, slot):
        if self.owned[slot]:              # drop the bookkeeping, keep refs
            self.owned[slot] = []
            self.tables[slot, :] = 0
            self.dirty = True
            return
        return real_release(self, slot)

    monkeypatch.setattr(PagedCacheManager, "release", leaky_release)
    with pytest.raises(AuditError):
        eng.serve(copy.deepcopy(reqs))


def test_pool_stats_carry_audit_fields():
    eng = _engine()
    eng.serve(_reqs(3))
    p = eng.last_pool_stats
    assert p.audit_ok and p.audit_errors == []
    assert p.audit_orphan_pages == 0 and p.audit_refcount_mismatches == 0


def test_audit_manager_function_directly():
    mgr = PagedCacheManager(num_pages=8, page_size=4, slots=2, max_seq=16)
    mgr.admit(0, 5)
    mgr.admit(1, 4)
    rep = audit_manager(mgr)
    assert rep.ok and rep.errors == []
    mgr.release(0)
    mgr.release(1)
    assert audit_manager(mgr).ok


# ---------------------------------------------------------------------------
# property test: random schedules -> parity + partition + leak-freedom
# ---------------------------------------------------------------------------

def _random_sweep_once(eng, reqs, base, seed):
    fs = FaultSchedule.random(seed, uids=tuple(r.uid for r in reqs),
                              max_step=16)
    out = eng.serve(copy.deepcopy(reqs), faults=fs)
    stt = _statuses(eng)
    assert set(stt) == {r.uid for r in reqs}
    assert all(v in TERMINAL_STATUSES for v in stt.values()), (fs, stt)
    for u, toks in out.items():
        assert stt[u] == STATUS_OK
        assert toks == base[u], (fs, u)
    for u, v in stt.items():
        if v == STATUS_OK:
            assert u in out
    p = eng.last_pool_stats
    assert p.audit_ok, (fs, p.audit_errors)
    assert p.used_pages == 0, fs


@pytest.mark.slow
def test_random_fault_schedules_parity_sweep():
    reqs = _reqs(5, mlo=5, mhi=9)
    eng = _engine(max_recoveries=16)
    base = eng.serve(copy.deepcopy(reqs))
    for seed in range(40):
        _random_sweep_once(eng, reqs, base, seed)


def test_random_fault_schedules_parity_smoke():
    reqs = _reqs(4, mlo=4, mhi=8)
    eng = _engine(max_recoveries=16)
    base = eng.serve(copy.deepcopy(reqs))
    for seed in range(6):
        _random_sweep_once(eng, reqs, base, seed)


if _HAVE_HYPOTHESIS:
    # one shared engine across examples: every example re-jitting its own
    # step functions would turn a property test into a compile benchmark
    _PROP = {}

    def _prop_fixture():
        if not _PROP:
            _PROP["reqs"] = _reqs(4, mlo=4, mhi=8)
            _PROP["eng"] = _engine(max_recoveries=16)
            _PROP["base"] = _PROP["eng"].serve(
                copy.deepcopy(_PROP["reqs"]))
        return _PROP["eng"], _PROP["reqs"], _PROP["base"]

    @settings(max_examples=10, deadline=None)
    @given(seed=hyp_st.integers(min_value=0, max_value=10_000))
    def test_random_fault_schedule_property(seed):
        """For ANY seeded schedule: statuses partition the request set,
        surviving outputs are bit-identical to the fault-free run, and
        the allocator ends leak-free."""
        eng, reqs, base = _prop_fixture()
        _random_sweep_once(eng, reqs, base, seed)
