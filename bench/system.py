"""The system under test, driven as a user drives it.

This file, and each family's ``program_config``, are all of the
benchmark that imports the program: it builds
``repro.serve.engine.ServeEngine`` (paged layout) over the benchmark's
weights and the family's model, warms up the shapes a traffic mix will
use, and drives the timed window through ``repro.serve.async_engine.
AsyncServeEngine`` on the wall clock.  It records, per request, when it
was due, when each token reached the client and what the engine's
ledger says (admission time, cached prefix tokens), and the engine's
per-round time series.

In a traced run it also wraps the engine's jitted steps to note the
lengths each step actually computed (for the family's work
functions) and names the host's scheduler phases with profiler
annotations (for the idle-gap breakdown); nothing of this runs
otherwise.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np


def build_engine(family, conf: dict, dims: dict, params):
    """``ServeEngine`` with the configuration's engine settings over the
    family's model, serving in bfloat16 as the published config
    states."""
    import jax.numpy as jnp

    from repro.models.lm import Model
    from repro.serve.engine import ServeEngine

    model = Model(family.program_config(conf, dims),
                  param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)
    return ServeEngine(model, params, **conf["engine"])


# ------------------------------------------------------------- warm-up
def _round_up(x: int, block: int) -> int:
    return max(block, -(-x // block) * block)


def warmup_lengths(engine, traffic: dict) -> List[List[int]]:
    """Prompt lengths that make the engine compile every shape the mix
    can reach: each prefill bucket of its prompt range and each decode
    attention bucket of its context range.  Returned in groups, one
    group per decode bucket (a step's bucket is set by its longest live
    context, so each bucket needs a session of its own)."""
    pb, ab, max_seq = engine.prompt_block, engine.attend_block, \
        engine.max_seq
    p, o = traffic["prompt"], traffic["output"]
    system = traffic["tenants"]["system_len"] if traffic.get("tenants") \
        else 0
    lo, hi = system + p["min"], system + p["max"]
    prompts = {min(max_seq, b) for b in range(_round_up(lo, pb),
                                              _round_up(hi, pb) + 1, pb)}
    by_attend: Dict[int, List[int]] = {}
    for n in sorted(prompts):
        by_attend.setdefault(min(max_seq, _round_up(n + 1, ab)), []).append(n)
    top = min(max_seq, _round_up(hi + o["max"], ab))
    edge = ab
    while edge < top:
        # a context just past each bucket edge: its decode steps run in
        # the next bucket up
        a = min(max_seq, _round_up(edge + 2, ab))
        if a not in by_attend:
            by_attend[a] = [edge + 1]
        edge += ab
    return [by_attend[a] for a in sorted(by_attend)]


def warmup(engine, traffic: dict, vocab: int) -> int:
    """Serve the warm-up sessions; returns the number of requests."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(0)
    n = 0
    for group in warmup_lengths(engine, traffic):
        reqs = [Request(uid=i, prompt=rng.integers(0, vocab, k).tolist(),
                        max_new_tokens=3) for i, k in enumerate(group)]
        engine.serve(reqs)
        bad = {u: s["status"] for u, s in engine.last_stats.items()
               if isinstance(u, int) and s["status"] != "ok"}
        if bad:
            raise RuntimeError(f"warm-up requests did not finish: {bad}")
        n += len(reqs)
    # a preemption resume re-matches its own pages and prefills the rest
    # through the suffix path; with tenants every warm request does
    if traffic.get("tenants"):
        system = rng.integers(0, vocab, traffic["tenants"]["system_len"])
        p = traffic["prompt"]
        sizes = range(_round_up(p["min"], engine.prompt_block),
                      _round_up(p["max"], engine.prompt_block) + 1,
                      engine.prompt_block)
        reqs = [Request(uid=0, prompt=system.tolist() + [1],
                        max_new_tokens=2)]
        reqs += [Request(uid=i + 1,
                         prompt=system.tolist()
                         + rng.integers(0, vocab, k).tolist(),
                         max_new_tokens=2) for i, k in enumerate(sizes)]
        for r in reqs:
            engine.serve([r])
        n += len(reqs)
    return n


# ----------------------------------------------------------- recording
@dataclasses.dataclass
class Record:
    """One request as the client saw it (host perf_counter seconds)."""
    uid: int
    due: float
    n_prompt: int
    max_new: int
    prompt: np.ndarray
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: Optional[str] = None
    admitted: Optional[float] = None
    cached_prefix: int = 0
    cancelled_by_bench: bool = False


@dataclasses.dataclass
class WorkLog:
    """Lengths the jitted steps computed while ``active`` (traced run),
    and when the trace began (host perf_counter seconds)."""
    active: bool = False
    started: Optional[float] = None
    decode: List[List[int]] = dataclasses.field(default_factory=list)
    prefill: List[int] = dataclasses.field(default_factory=list)
    suffix: List[tuple] = dataclasses.field(default_factory=list)


def instrument(engine, state: Callable, log: WorkLog):
    """Wrap the engine's jitted steps (and name its host phases for the
    profiler).  ``state()`` returns the live scheduler state."""
    import jax

    step, bucket, suffix = (engine._paged_step, engine._prefill_bucket,
                            engine._suffix_prefill)

    def paged_step(*a, **k):
        if log.active:
            st = state()
            log.decode.append([st.slot_pos[s] + 1 for s in st.live])
        return step(*a, **k)

    def prefill_bucket(params, batch, last_pos):
        if log.active:
            log.prefill.extend(int(x) + 1 for x in np.asarray(last_pos))
        return bucket(params, batch, last_pos)

    def suffix_prefill(params, pool, tables, toks, start, last, attend):
        if log.active:
            log.suffix.extend(zip((int(x) for x in np.asarray(start)),
                                  (int(x) + 1 for x in np.asarray(last))))
        return suffix(params, pool, tables, toks, start, last, attend)

    engine._paged_step = paged_step
    engine._prefill_bucket = prefill_bucket
    engine._suffix_prefill = suffix_prefill

    def named(label, fn):
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(label):
                return fn(*a, **k)
        return wrapped

    for attr, label in (("dispatch_round", "engine.round"),
                        ("commit_round", "engine.commit"),
                        ("_admit_shared", "engine.admit"),
                        ("_grow_or_preempt", "engine.grow"),
                        ("_timed_dispatch", "engine.dispatch")):
        setattr(engine, attr, named(label, getattr(engine, attr)))


# -------------------------------------------------------------- drivers
@dataclasses.dataclass
class Window:
    """Host perf_counter times of the run's phases."""
    start: float = 0.0          # arrivals begin
    open: float = 0.0           # measured window opens
    close: float = 0.0
    end: float = 0.0            # last counted request finished or given up
    session_t0: float = 0.0     # the engine session's clock origin
    lateness: List[float] = dataclasses.field(default_factory=list)


class Timeline:
    """Callbacks at fixed offsets from the window's opening, run between
    engine rounds (the event loop only turns between them)."""

    def __init__(self, events):
        self.events = sorted(events, key=lambda e: e[0])

    async def run(self, t_open: float):
        for offset, fn in self.events:
            delay = t_open + offset - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            fn()


async def _consume(stream, rec: Record):
    async for tok in stream:
        rec.times.append(time.perf_counter())
        rec.tokens.append(tok)
    rec.status = stream.status


def _request(r):
    from repro.serve.engine import Request

    return Request(uid=r.uid, prompt=r.prompt.tolist(),
                   max_new_tokens=r.max_new)


async def open_loop(engine, reqs, *, ramp_s: float, seconds: float,
                    drain_s: float, timeline: Timeline,
                    on_start: Callable = lambda srv: None):
    """Submit each request at its due time; the window is
    ``[ramp_s, ramp_s + seconds)`` after the first arrival.  Arrivals
    continue after the window until every request due in it has ended
    (or ``drain_s`` has passed); those left are cancelled."""
    from repro.serve.async_engine import AsyncServeEngine

    srv = AsyncServeEngine(engine, clock="wall")
    win = Window()
    records: Dict[int, Record] = {}
    tasks = []
    async with srv:
        on_start(srv)
        win.start = time.perf_counter() + 0.01
        win.open = win.start + ramp_s
        win.close = win.open + seconds
        give_up = win.close + drain_s
        counted = [r.uid for r in reqs
                   if ramp_s <= r.due_s < ramp_s + seconds]
        tl = asyncio.get_running_loop().create_task(timeline.run(win.open))

        def counted_done():
            return all(records.get(u) is not None
                       and records[u].status is not None for u in counted)

        for r in reqs:
            due = win.start + r.due_s
            if due >= win.close and counted_done():
                break
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            now = time.perf_counter()
            win.lateness.append(now - due)
            rec = Record(uid=r.uid, due=due, n_prompt=len(r.prompt),
                         max_new=r.max_new, prompt=r.prompt)
            records[r.uid] = rec
            tasks.append(asyncio.get_running_loop().create_task(
                _consume(await srv.submit(_request(r)), rec)))
        while not counted_done() and time.perf_counter() < give_up:
            await asyncio.sleep(0.01)
        win.end = time.perf_counter()
        for rec in records.values():
            if rec.status is None:
                rec.cancelled_by_bench = True
                srv.cancel(rec.uid)
        await tl
        await asyncio.gather(*tasks)
    win.session_t0 = srv._st.t0
    _ledger(engine, win.session_t0, records)
    return win, records


async def closed_loop(engine, clients, *, ramp_s: float, seconds: float,
                      timeline: Timeline,
                      on_start: Callable = lambda srv: None):
    """Each client sends its next request when its last one ends, until
    the window closes; requests still running then are cancelled."""
    from repro.serve.async_engine import AsyncServeEngine

    srv = AsyncServeEngine(engine, clock="wall")
    win = Window()
    records: Dict[int, Record] = {}

    async def client(reqs):
        for r in reqs:
            now = time.perf_counter()
            if now >= win.close:
                return
            rec = Record(uid=r.uid, due=now, n_prompt=len(r.prompt),
                         max_new=r.max_new, prompt=r.prompt)
            records[r.uid] = rec
            await _consume(await srv.submit(_request(r)), rec)

    async with srv:
        on_start(srv)
        win.start = time.perf_counter()
        win.open = win.start + ramp_s
        win.close = win.open + seconds
        loop = asyncio.get_running_loop()
        tl = loop.create_task(timeline.run(win.open))
        tasks = [loop.create_task(client(c)) for c in clients]
        await asyncio.sleep(max(0.0, win.close - time.perf_counter()))
        win.end = time.perf_counter()
        for rec in records.values():
            if rec.status is None:
                rec.cancelled_by_bench = True
                srv.cancel(rec.uid)
        await tl
        await asyncio.gather(*tasks)
    win.session_t0 = srv._st.t0
    _ledger(engine, win.session_t0, records)
    return win, records


def _ledger(engine, t0: float, records: Dict[int, Record]):
    """Copy the engine's per-request ledger into the records (its times
    are seconds since the session opened at ``t0``)."""
    for uid, rec in records.items():
        s = engine.last_stats.get(uid, {})
        if "admitted_s" in s:
            rec.admitted = t0 + s["admitted_s"]
        rec.cached_prefix = int(s.get("cached_prefix_tokens", 0))


def timeseries(engine, srv_t0: float) -> Dict[str, np.ndarray]:
    """The engine's per-round series, with absolute host times."""
    ts = engine.last_stats.get("timeseries", {})
    out = {k: np.asarray(v) for k, v in ts.items()}
    if "t_s" in out:
        out["t"] = out["t_s"] + srv_t0
    return out
