"""Spans and counters of the serving engine (``repro.serve.trace``).

One short open-loop session (tiny model, paged cache, prefix sharing and
the round pipeline on), served once under the profiler and read back
with ``ProfileData``, and once without it.  The spans must nest as the
engine's phases do, the counters must equal what wrappers around the
jitted steps observe, and without a profiler the outputs must be the
ones the engine gave before it had spans.
"""

import asyncio
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.registry import reduced_config
from repro.kernels.decode_attention.decode_attention import pages_per_block
from repro.models.lm import Model
from repro.serve import Request, ServeEngine
from repro.serve.async_engine import AsyncServeEngine
from repro.serve.engine import COUNTERS
from repro.serve.kv_cache import TRASH_PAGE
from repro.serve.trace import SPANS, column
from repro.serve.workload import TimedRequest

# the session's outputs before the engine had spans or counters
GOLDEN = {0: [505, 505, 505, 505, 505, 505], 1: [437, 365, 260],
          2: [29, 15, 101, 390, 198, 15, 404],
          3: [241, 441, 208, 348, 197, 151],
          4: [510, 366, 71, 297, 510, 407, 475, 297],
          5: [109, 346, 59, 428, 428]}
_EKW = dict(max_seq=64, batch_slots=3, temperature=0.0, seed=0,
            cache_layout="paged", page_size=8)


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config("qwen2-1.5b")
    m = Model(cfg, compute_dtype=jnp.float32)
    return cfg, m, m.init(jax.random.PRNGKey(1))


def _timed(vocab):
    rng = np.random.default_rng(7)
    return [TimedRequest(arrival_s=float(i // 2), request=Request(
        uid=i, prompt=rng.integers(0, vocab, int(rng.integers(5, 30))
                                   ).tolist(),
        max_new_tokens=int(rng.integers(3, 9)))) for i in range(6)]


def _session(model, observe=None, **kw):
    """Serve the six requests open loop on the round clock; returns the
    outputs and the engine.  ``observe(engine, st)`` runs once the
    session state exists."""
    cfg, m, params = model
    eng = ServeEngine(m, params, **{**_EKW, "prefix_sharing": True,
                                    "pipeline": True, **kw})
    timed = _timed(cfg.vocab)

    async def run():
        async with AsyncServeEngine(eng, clock="round") as srv:
            if observe is not None:
                observe(eng, srv._st)
            await srv.run_workload(timed)
            return await srv.close()

    return asyncio.run(run()), eng, timed


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """The session under the profiler: outputs, engine and the serve.*
    events of the engine's thread as (name, start_ns, end_ns, stats)."""
    d = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(d):
        out, eng, timed = _session(model)
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    lines = []
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("serve.")]
            if any(n == "serve.round" for n, *_ in evs):
                lines.append(evs)
    assert len(lines) == 1, "the spans belong on one thread"
    return out, eng, timed, lines[0]


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_fetches_lie_inside_rounds(traced):
    _, _, _, events = traced
    rounds = _named(events, "serve.round")
    fetches = (_named(events, "serve.fetch")
               + _named(events, "serve.prefill_fetch"))
    assert fetches
    for _, a, b, _ in fetches:
        assert any(ra <= a and b <= rb for _, ra, rb, _ in rounds)


def test_one_fetch_per_step_and_per_admission(traced):
    _, eng, timed, events = traced
    c = eng.last_stats["counters"]
    assert len(_named(events, "serve.fetch")) == c["decode_steps"] > 0
    # prefix sharing admits one request per prefill
    assert (len(_named(events, "serve.prefill_fetch")) == c["admissions"]
            == len(timed))


def test_prefill_spans_carry_uid_tokens_and_bucket(traced):
    _, eng, timed, events = traced
    prefills = _named(events, "serve.prefill")
    assert sorted(str(s["uid"]) for *_, s in prefills) == sorted(
        str(t.request.uid) for t in timed)
    lens = {str(t.request.uid): len(t.request.prompt) for t in timed}
    for *_, s in prefills:
        assert s["tokens"] == lens[str(s["uid"])]
        assert s["bucket"] >= s["tokens"]


def test_every_phase_is_spanned(traced):
    _, eng, _, events = traced
    seen = {e[0] for e in events}
    # no request is swapped out in this session
    assert seen == set(SPANS) - {"serve.swap_fetch"}
    ts = eng.last_stats["timeseries"]
    n = len(ts["round"])
    for name in SPANS:
        assert len(ts[column(name)]) == n
    assert sum(ts["fetch_s"]) > 0 and sum(ts["commit_s"]) > 0


def _kernel_keys(eng, pool, tables, pos, attend):
    """Keys the paged decode kernel visits, from the step's own inputs:
    rows whose table starts at the trash page read nothing, the others
    ``pos + 1`` keys within the bucket's table, in compute blocks."""
    cfg, page = eng.model.cfg, eng.page_size
    nb = -(-attend // page)
    block = page * pages_per_block(
        nb, page, cfg.n_heads // cfg.n_kv_heads, cfg.n_kv_heads,
        cfg.d_head, pool["k_pages"].dtype.itemsize)
    tables, pos = np.asarray(tables)[:, :nb], np.asarray(pos)
    keys = np.where(tables[:, 0] == TRASH_PAGE, 0,
                    np.minimum(pos + 1, nb * page))
    return int(sum(-(-int(k) // block) * block for k in keys))


def test_counters_equal_what_the_steps_observe(model):
    seen = {"grid": 0, "ctx": 0, "steps": 0, "prefill": []}

    def observe(eng, st):
        step, bucket = eng._paged_step, eng._prefill_bucket

        def paged_step(*a):
            seen["steps"] += 1
            seen["grid"] += _kernel_keys(eng, a[1], a[2], a[4], a[-1])
            seen["ctx"] += sum(st.slot_pos[s] + 1 for s in st.live)
            return step(*a)

        def prefill_bucket(params, batch, last_pos):
            seen["prefill"] += [int(x) + 1 for x in np.asarray(last_pos)]
            return bucket(params, batch, last_pos)

        eng._paged_step, eng._prefill_bucket = paged_step, prefill_bucket

    _, eng, timed = _session(model, observe)
    c = eng.last_stats["counters"]
    assert set(c) == set(COUNTERS)
    assert c["decode_steps"] == seen["steps"]
    assert c["decode_grid_tokens"] == seen["grid"]
    assert c["decode_ctx_tokens"] == seen["ctx"]
    assert 0 < c["decode_ctx_tokens"] < c["decode_grid_tokens"]
    lens = [len(t.request.prompt) for t in timed]
    assert c["prefill_tokens"] == sum(lens) == sum(seen["prefill"])
    assert c["prefill_causal_keys"] == sum(n * (n + 1) // 2 for n in lens)
    assert c["admissions"] == len(timed)
    # the timeseries carries the counters, cumulative, one row per round
    for name in COUNTERS:
        assert eng.last_stats["timeseries"][name][-1] == c[name]


def test_outputs_unchanged_without_a_profiler(model):
    out, _, _ = _session(model)
    assert out == GOLDEN


def test_traced_outputs_match_untraced(traced):
    assert traced[0] == GOLDEN


def test_swap_copies_are_fetch_spans(model):
    """A swap-tier preemption's device-to-host copy is a swap_fetch
    phase, pipelined or serial, and the outputs agree."""
    cfg, m, params = model
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(
        0, cfg.vocab, int(rng.integers(5, 12))).tolist(),
        max_new_tokens=int(rng.integers(6, 12))) for i in range(6)]
    outs = []
    for pipeline in (True, False):
        eng = ServeEngine(m, params, **{**_EKW, "batch_slots": 2,
                                        "num_pages": 4, "preempt": "swap",
                                        "pipeline": pipeline})
        outs.append(eng.serve([dataclasses.replace(r, generated=None)
                               for r in reqs]))
        assert eng.preemptions > 0
        assert sum(eng.last_stats["timeseries"]["swap_fetch_s"]) > 0
    assert outs[0] == outs[1]
