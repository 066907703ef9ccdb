"""Overlapped round pipeline: dispatch/commit decode with host work in
the gap must be a pure latency optimization.

The contract under test: ``pipeline=True`` (async dispatch, commit at
the next round's barrier, D2H swap copies deferred) returns bit-identical
outputs to ``pipeline=False`` (today's serial round) for every request —
across preemption, injected NaN/kernel faults with recovery, speculative
decoding, chunked prefill, and a disaggregated 2-replica cluster.  The
only visible difference allowed is one extra trailing round per session
(the last step's commit)."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models.lm import Model
from repro.serve import (
    STATUS_OK,
    Fault,
    FaultSchedule,
    Request,
    ServeEngine,
    make_cluster,
)
from repro.serve.calibrate import (
    DEFAULT_COST_MODEL,
    CostModel,
    calibrate,
)

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


_EKW = {"max_seq": 48, "batch_slots": 2, "temperature": 0.0, "seed": 0,
        "cache_layout": "paged", "page_size": 8}


def _engine(**kw):
    cfg, model, params = _model()
    return ServeEngine(model, params, **{**_EKW, **kw})


def _reqs(n, seed=3, plo=3, phi=12, mlo=2, mhi=7, **fields):
    cfg, _, _ = _model()
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(
                        0, cfg.vocab,
                        size=int(rng.integers(plo, phi))).tolist(),
                    max_new_tokens=int(rng.integers(mlo, mhi)), **fields)
            for i in range(n)]


def _fresh(reqs):
    return [dataclasses.replace(r, generated=None) for r in reqs]


def _both(reqs, faults=None, **kw):
    """Serve the same batch serial and pipelined; return both engines'
    (results, stats)."""
    out = {}
    for pipeline in (False, True):
        eng = _engine(pipeline=pipeline, **kw)
        fs = copy.deepcopy(faults) if faults is not None else None
        res = eng.serve(_fresh(reqs), faults=fs)
        out[pipeline] = (res, eng.last_stats)
    return out[False], out[True]


# ------------------------------------------------------------ plain parity
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_pipeline_parity(temperature):
    (ref, _), (out, _) = _both(_reqs(6), temperature=temperature)
    assert out == ref


def test_pipeline_trailing_round_only():
    """The pipelined session runs exactly one extra round (the trailing
    commit of the final step)."""
    (_, s_ref), (_, s_pipe) = _both(_reqs(5))
    assert (s_pipe["timeseries"]["round"][-1]
            == s_ref["timeseries"]["round"][-1] + 1)


def test_pipeline_parity_under_preemption():
    """A pool too small for the batch forces preempt-and-requeue churn;
    outputs must not move."""
    reqs = _reqs(6, mlo=6, mhi=12)
    for preempt in ("requeue", "swap"):
        (ref, s_ref), (out, s_pipe) = _both(
            reqs, num_pages=4, preempt=preempt)
        assert out == ref
        ref_pre = sum(s_ref[r.uid]["preemptions"] for r in reqs)
        pipe_pre = sum(s_pipe[r.uid]["preemptions"] for r in reqs)
        assert ref_pre == pipe_pre and ref_pre > 0


def test_pipeline_swap_deferred_materialization():
    """Pipelined swap-out defers the D2H copy past the next dispatch;
    the resumed outputs are still bit-identical and every handle drains
    by session end."""
    reqs = _reqs(6, mlo=6, mhi=12)
    (ref, s_ref), (out, s_pipe) = _both(reqs, num_pages=4, preempt="swap")
    assert out == ref
    assert sum(s_pipe[r.uid].get("swap_ins", 0) for r in reqs) > 0


def test_pipeline_parity_under_faults_with_recovery():
    """Injected NaN quarantine + a kernel failure with step-restart
    recovery: the pipelined run discards or drains its pending round
    atomically and replays identically."""
    reqs = _reqs(6, mlo=6, mhi=10)
    fs = FaultSchedule([Fault("nan", step=2, uid=1, span=2),
                        Fault("kernel", step=6)])
    (ref, s_ref), (out, s_pipe) = _both(reqs, faults=fs)
    assert out == ref
    for r in reqs:
        assert s_pipe[r.uid]["status"] == s_ref[r.uid]["status"]
    assert s_pipe[1]["status"] != STATUS_OK  # the quarantined request


def test_pipeline_parity_page_corruption_and_cancel():
    reqs = _reqs(6)
    fs = FaultSchedule([Fault("page_corruption", step=2),
                        Fault("cancel", step=3, uid=2)], seed=9)
    (ref, s_ref), (out, s_pipe) = _both(reqs, faults=fs, audit=True)
    assert out == ref
    for r in reqs:
        assert s_pipe[r.uid]["status"] == s_ref[r.uid]["status"]


def test_pipeline_parity_spec_decode():
    reqs = _reqs(5, mlo=4, mhi=9)
    (ref, _), (out, s_pipe) = _both(reqs, spec_k=4)
    assert out == ref
    assert sum(s_pipe[r.uid].get("spec_tokens", 0) for r in reqs) > 0


def test_pipeline_parity_chunked_prefill():
    reqs = _reqs(5, plo=9, phi=16)
    (ref, _), (out, _) = _both(reqs, prefill_budget=8)
    assert out == ref


def test_pipeline_parity_cluster_disaggregated():
    """2-replica disaggregated fleet with pipelined workers == the
    serial direct engine."""
    cfg, model, params = _model()
    reqs = _reqs(6)
    ref = _engine(pipeline=False).serve(_fresh(reqs))
    c = make_cluster(model, params, replicas=2, disaggregate=True,
                     pipeline=True, **_EKW)
    out = c.serve(_fresh(reqs))
    assert out == ref
    assert c.audit_report.ok


def test_pipeline_timeseries_phases():
    """The pipelined timeseries reports dispatch/fetch/commit/overlap
    phase timings from the engine's spans, and the SLA summary rolls
    them up.  The fetch (the blocking wait) and the commit (host
    accounting after it) are separate phases."""
    eng = _engine(pipeline=True)
    eng.serve(_fresh(_reqs(4)))
    ts = eng.last_stats["timeseries"]
    n = len(ts["round"])
    assert len(ts["dispatch_s"]) == len(ts["fetch_s"]) \
        == len(ts["commit_s"]) == len(ts["overlap_s"]) == n
    assert any(v > 0 for v in ts["overlap_s"])
    assert sum(ts["fetch_s"]) > 0 and sum(ts["commit_s"]) > 0
    # every phase lies inside its round
    for col in ("dispatch_s", "fetch_s", "commit_s", "overlap_s"):
        assert all(v <= r for v, r in zip(ts[col], ts["round_s"]))
    rounds = eng.last_stats["sla"]["rounds"]
    assert rounds["n"] == n
    assert rounds["overlap_s_mean"] > 0
    assert rounds["fetch_s_mean"] > 0
    # serial rounds never report overlap
    eng = _engine(pipeline=False)
    eng.serve(_fresh(_reqs(4)))
    assert all(v == 0.0 for v in
               eng.last_stats["timeseries"]["overlap_s"])


# ------------------------------------------------------- deadline ordering
def test_slack_orders_preemption_victims():
    """Deadline-aware preemption: with priorities equal, the deadline-
    less request (infinite slack) yields its slot before the request
    racing a deadline — flipping the old newest-first outcome when the
    deadline request is newer."""
    eng = _engine()
    st = eng._open_session([], None)
    # two live slots: uid 0 (older, no deadline), uid 1 (newer, tight
    # deadline).  Old rule (priority, admit_seq) picks the newer uid 1;
    # slack-first must pick uid 0.
    for uid, deadline in ((0, None), (1, 10_000.0)):
        req = Request(uid=uid, prompt=[1, 2, 3], max_new_tokens=4,
                      deadline_ms=deadline)
        eng._register(st, req)
        st.live[uid] = req
        st.admit_seq[uid] = uid
    victim = eng._preempt_victim(st)
    assert victim == 0
    # without deadlines anywhere, ties fall back to the old rule exactly
    st.live[1] = dataclasses.replace(st.live[1], deadline_ms=None)
    st.has_deadlines = False
    assert eng._preempt_victim(st) == 1


def test_slack_parity_without_deadlines():
    """No request carries a deadline -> every slack is +inf and the
    slack-aware ordering must reproduce the old outputs bit-for-bit
    (guarded by the preemption-churn parity test above); here we pin the
    stats too."""
    reqs = _reqs(6, mlo=6, mhi=12)
    (ref, s_ref), (out, s_pipe) = _both(reqs, num_pages=4)
    assert out == ref
    assert ([s_ref[r.uid]["preemptions"] for r in reqs]
            == [s_pipe[r.uid]["preemptions"] for r in reqs])


# ------------------------------------------------------------- calibration
def test_calibrate_cost_model():
    cfg, model, params = _model()
    cm = calibrate(model, params, max_seq=32, repeats=1)
    assert cm.source == "measured"
    assert cm.swap_gbps > 0 and cm.decode_flops_s > 0


def test_engine_cost_model_wiring():
    eng = _engine()
    assert eng.cost_model == DEFAULT_COST_MODEL
    explicit = CostModel(1e9, 1e12, source="explicit")
    eng = _engine(cost_model=explicit)
    assert eng.cost_model is explicit
    eng = _engine(preempt_calibrate=True)
    assert eng.cost_model.source == "measured"


def test_cost_model_steers_auto_preempt():
    """preempt=auto flips between swap and requeue as the measured
    figures move: an infinitely fast link swaps, an infinitely fast
    model recomputes."""
    reqs = _reqs(6, mlo=6, mhi=12)
    swap_wins = CostModel(swap_gbps=1e15, decode_flops_s=1e3)
    eng = _engine(num_pages=4, preempt="auto", cost_model=swap_wins)
    eng.serve(_fresh(reqs))
    assert eng.last_pool_stats.swap_outs > 0
    recompute_wins = CostModel(swap_gbps=1e-3, decode_flops_s=1e15)
    eng = _engine(num_pages=4, preempt="auto", cost_model=recompute_wins)
    eng.serve(_fresh(reqs))
    assert eng.last_pool_stats.swap_outs == 0


# ------------------------------------------------------------- hypothesis
if _HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(seed=hyp_st.integers(0, 2**16),
           n=hyp_st.integers(2, 6),
           pages=hyp_st.sampled_from([16, 24, 48]),
           temperature=hyp_st.sampled_from([0.0, 0.7]))
    def test_property_pipeline_toggle_is_invisible(seed, n, pages,
                                                   temperature):
        """Random schedules (prompt/output lengths, pool pressure,
        temperature) serve bit-identically with pipeline toggled."""
        reqs = _reqs(n, seed=seed)
        (ref, _), (out, _) = _both(reqs, num_pages=pages,
                                   temperature=temperature)
        assert out == ref
