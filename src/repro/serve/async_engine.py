"""Async event-loop server over the serving engine's session primitives.

``ServeEngine.serve()`` is closed-loop: hand it a batch, block until the
last request drains.  This module drives the same scheduler open-loop:

  * requests arrive on a clock (``submit()`` any time; ``run_workload``
    replays a :mod:`repro.serve.workload` arrival process),
  * tokens stream back through per-request async iterators
    (:class:`TokenStream`) as each scheduler round commits them,
  * the engine's rounds interleave with the event loop — one blocking
    jitted round, then a yield, so submissions and consumers run
    between rounds (the jitted step is the unit of work; this is a
    cooperative server, not a threaded one).

Everything the scheduler decides — admission order, chunked prefill,
preemption, shedding, fault recovery — happens inside the engine's own
``_round``, shared verbatim with the closed-loop path.  Combined with
``(uid, position)``-keyed sampling that makes outputs independent of
batch composition, streamed tokens are bit-identical to what a batch
``serve()`` of the same admitted set returns; the open-loop chaos gates
in benchmarks/serve_openloop.py are built on that equivalence.

Two clocks:

  wall   (default) ``run_workload`` sleeps real seconds between
         arrivals.  Honest latency numbers; arrival edges blur by up to
         one round (the event loop blocks while a round runs).
  round  arrivals land at ``int(arrival_s / round_time_s)`` scheduler
         rounds; idle rounds tick the clock toward the next arrival.
         Fully deterministic — same workload + faults + seed is the
         same admission sequence, statuses, and tokens, which is what
         CI gates on.

SLA/timeseries observability rides the engine: after ``close()``,
``engine.last_stats["sla"]`` and ``["timeseries"]`` cover the session.
One session per ``AsyncServeEngine``; the wrapped engine must not serve
another call while the session is live.
"""

from __future__ import annotations

import asyncio
import heapq
import itertools
import time
from collections import deque
from typing import Dict, List, Optional

from repro.serve.engine import STATUS_OK, Request, ServeEngine
from repro.serve.trace import span
from repro.serve.workload import TimedRequest

_DONE = object()


class TokenStream:
    """Per-request async iterator: yields tokens as the scheduler
    commits them, then raises ``StopAsyncIteration`` once the request
    reaches a terminal status (``.status`` / ``.reason`` tell which;
    ``.tokens`` keeps everything delivered)."""

    def __init__(self, uid: int):
        self.uid = uid
        self.tokens: List[int] = []
        self.status: Optional[str] = None
        self.reason: Optional[str] = None
        self._q: asyncio.Queue = asyncio.Queue()
        self._sent = 0          # engine-side cursor into req.generated
        self._closed = False
        self._exhausted = False

    # ---- engine side -----------------------------------------------------
    def _push(self, tok: int):
        self.tokens.append(tok)
        self._q.put_nowait(tok)

    def _close(self, status: str, reason: Optional[str] = None):
        if self._closed:
            return
        self._closed = True
        self.status, self.reason = status, reason
        self._q.put_nowait(_DONE)

    def _fail(self, exc: BaseException):
        if self._closed:
            return
        self._closed = True
        self.status = "failed"
        self.reason = f"{type(exc).__name__}: {exc}"
        self._q.put_nowait(exc)

    # ---- consumer side ---------------------------------------------------
    def __aiter__(self):
        return self

    async def __anext__(self) -> int:
        if self._exhausted:
            raise StopAsyncIteration
        item = await self._q.get()
        if item is _DONE:
            self._exhausted = True
            raise StopAsyncIteration
        if isinstance(item, BaseException):
            self._exhausted = True
            raise item
        return item

    async def drain(self) -> List[int]:
        """Consume the rest of the stream; returns all tokens."""
        async for _ in self:
            pass
        return list(self.tokens)


class AsyncServeEngine:
    """Open-loop driver: submissions + token streams around one engine
    session.  Use as an async context manager, or ``submit()`` /
    ``close()`` by hand."""

    def __init__(self, engine: ServeEngine, *, faults=None,
                 clock: str = "wall", round_time_s: float = 1.0,
                 idle_poll_s: float = 0.002,
                 backpressure_watermark: Optional[int] = None):
        if clock not in ("wall", "round"):
            raise ValueError(f"clock must be 'wall' or 'round'; "
                             f"got {clock!r}")
        if backpressure_watermark is not None and backpressure_watermark < 1:
            raise ValueError("backpressure_watermark must be >= 1; "
                             f"got {backpressure_watermark}")
        self.engine = engine
        self.clock = clock
        self.round_time_s = round_time_s
        self.idle_poll_s = idle_poll_s
        # awaitable backpressure: submit() blocks while the waiting queue
        # sits at/above this depth, instead of letting the engine shed
        self.backpressure_watermark = backpressure_watermark
        self._round_evt = asyncio.Event()
        self._faults = faults
        self._st = None
        self._task: Optional[asyncio.Task] = None
        self._pending: deque = deque()     # (request, stream, arrival_round)
        self._scheduled: list = []         # heap of (round, tie, req, stream)
        self._tiebreak = itertools.count()
        self._streams: Dict[int, tuple] = {}
        self._open: set = set()
        self._wake = asyncio.Event()
        self._closing = False
        self._error: Optional[BaseException] = None
        self._results: Dict[int, List[int]] = {}

    # ------------------------------------------------------------ lifecycle
    async def __aenter__(self) -> "AsyncServeEngine":
        self._ensure_started()
        return self

    async def __aexit__(self, exc_type, exc, tb):
        if exc_type is None:
            await self.close()
        else:
            self._closing = True
            self._wake.set()

    def _ensure_started(self):
        if self._task is not None:
            return
        self._st = self.engine._open_session([], self._faults)
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def close(self) -> Dict[int, List[int]]:
        """Drain every in-flight request, finalize the session, and
        return {uid: tokens} for the OK ones (also kept in
        ``.results``).  Raises whatever failed the session."""
        if self._task is None:
            return {}
        self._closing = True
        self._wake.set()
        await self._task
        if self._error is not None:
            raise self._error
        return self._results

    @property
    def results(self) -> Dict[int, List[int]]:
        return self._results

    @property
    def last_stats(self):
        return self.engine.last_stats

    # ------------------------------------------------------------- requests
    async def submit(self, request: Request, *,
                     arrival_round: Optional[int] = None) -> TokenStream:
        """Enqueue a request; returns its token stream.  With the round
        clock, ``arrival_round`` (default: now) delays ingestion until
        that scheduler round.

        With ``backpressure_watermark`` set, this call *awaits* while
        the waiting queue (including not-yet-ingested submissions) is at
        or above the watermark — the submitter slows down instead of the
        engine shedding, which is the right trade whenever the caller
        can hold the request more cheaply than the server can reject it
        (the cluster front-end holds requests for an idle replica this
        way).  Without the watermark, submit never yields — co-arriving
        requests co-admit, which round-clock determinism depends on."""
        self._ensure_started()
        self._check_live()
        if self.backpressure_watermark is not None:
            while self._depth() >= self.backpressure_watermark:
                self._round_evt.clear()
                self._wake.set()
                await self._round_evt.wait()
                self._check_live()
        stream = TokenStream(request.uid)
        self._pending.append((request, stream, arrival_round))
        self._wake.set()
        # deliberately no yield past this point: back-to-back submits
        # land in the same ingestion sweep, so co-arriving requests are
        # co-admitted (the round clock's determinism depends on it)
        return stream

    def _check_live(self):
        if self._error is not None:
            raise RuntimeError("serving session already failed") \
                from self._error
        if self._closing:
            raise RuntimeError("serving session is closing")

    def _depth(self) -> int:
        """Waiting-queue depth as backpressure sees it: the engine's
        shed-eligible queue plus everything submitted but not yet
        ingested (otherwise a burst of submits would all pass the
        watermark before the loop ingests any of them)."""
        return (self.engine._queue_depth(self._st)
                + len(self._pending) + len(self._scheduled))

    def cancel(self, uid: int):
        """Cancel ``uid`` (queued, prefilling, or live) at the next
        round; its stream ends with status 'cancelled'."""
        self.engine.cancel(uid)
        self._wake.set()

    async def run_workload(
            self, timed: List[TimedRequest]) -> Dict[int, List[int]]:
        """Replay an arrival process end to end: submit each request at
        its arrival time (wall sleeps, or scheduler rounds under the
        round clock), drain every stream, return the OK outputs."""
        order = sorted(timed, key=lambda t: t.arrival_s)
        streams = []
        if self.clock == "round":
            for tr in order:
                streams.append(await self.submit(
                    tr.request,
                    arrival_round=int(tr.arrival_s / self.round_time_s)))
        else:
            t0 = time.perf_counter()
            for tr in order:
                delay = tr.arrival_s - (time.perf_counter() - t0)
                if delay > 0:
                    await asyncio.sleep(delay)
                streams.append(await self.submit(tr.request))
        await asyncio.gather(*(s.drain() for s in streams))
        return {s.uid: list(s.tokens) for s in streams
                if s.status == STATUS_OK}

    # ------------------------------------------------------------ the loop
    async def _run(self):
        eng, st = self.engine, self._st
        try:
            while True:
                with span(st, "serve.ingest"):
                    self._ingest(st)
                work = bool(st.queue or st.live or st.prefilling
                            or st.pending is not None)
                arrivals = bool(self._scheduled or self._pending)
                if not work and not arrivals:
                    if self._closing:
                        break
                    await self._idle_wait()
                    continue
                if not work and self.clock != "round":
                    # wall clock: nothing runnable until the next submit
                    await self._idle_wait()
                    continue
                # round clock ticks through idle rounds to reach the
                # next scheduled arrival; otherwise this is one real
                # scheduler round (admission + decode step).  Pipelined,
                # the round commits the *previous* step and leaves this
                # round's dispatch in flight — arrival ingestion and
                # stream publishing below are exactly the host work the
                # overlap hides (streams lag one round; content is
                # bit-identical)
                if eng.pipeline:
                    eng.dispatch_round(st)
                else:
                    eng._round(st)
                with span(st, "serve.publish"):
                    self._publish(st)
                self._round_evt.set()   # re-check blocked submitters
                await asyncio.sleep(0)
            self._results = eng._finalize_session(st)
        except BaseException as exc:  # noqa: BLE001 — reported via close()
            self._error = exc
            try:
                eng._abort(st, exc)
                self._publish(st)
            finally:
                for uid in list(self._open):
                    stream, _ = self._streams[uid]
                    stream._fail(exc)
                    self._open.discard(uid)
        finally:
            # blocked submitters must never outlive the loop: wake them
            # so they observe _closing/_error and raise
            self._round_evt.set()

    async def _idle_wait(self):
        self._wake.clear()
        try:
            await asyncio.wait_for(self._wake.wait(), self.idle_poll_s)
        except asyncio.TimeoutError:
            pass

    def _ingest(self, st):
        while self._pending:
            req, stream, rnd = self._pending.popleft()
            if rnd is not None and self.clock == "round":
                heapq.heappush(self._scheduled,
                               (rnd, next(self._tiebreak), req, stream))
            else:
                self._admit_now(st, req, stream)
        # an arrival at round r is visible to round r (st.rnd is the
        # round that just ran; the next _round call runs st.rnd + 1)
        while self._scheduled and self._scheduled[0][0] <= st.rnd + 1:
            _, _, req, stream = heapq.heappop(self._scheduled)
            self._admit_now(st, req, stream)

    def _admit_now(self, st, req: Request, stream: TokenStream):
        if req.uid in self._streams or req.uid in st.stats:
            stream._fail(ValueError(
                f"duplicate request uid {req.uid}: the status ledger and "
                f"sampling keys are keyed by uid"))
            return
        self._streams[req.uid] = (stream, req)
        self._open.add(req.uid)
        self.engine._submit_open(st, req,
                                 now=time.perf_counter() - st.t0)

    def _publish(self, st):
        """Diff each tracked request's ``generated`` list into its
        stream (the list is shared across preemption resumes, so it only
        ever appends — the cursor never double-sends), then close
        streams whose request reached a terminal status."""
        for uid in list(self._open):
            stream, req = self._streams[uid]
            s = st.stats.get(uid)
            if s is None:
                continue
            status = s.get("status")
            if status is None or status == STATUS_OK:
                gen = req.generated or []
                while stream._sent < len(gen):
                    stream._push(gen[stream._sent])
                    stream._sent += 1
            if status is not None:
                stream._close(status, s.get("reason"))
                self._open.discard(uid)


async def serve_open_loop(engine: ServeEngine, timed: List[TimedRequest],
                          *, faults=None, clock: str = "round",
                          round_time_s: float = 1.0) -> Dict[int, List[int]]:
    """One-shot helper: replay ``timed`` through a fresh session and
    return the OK outputs (``engine.last_stats`` carries the SLA
    summary).  The benchmark and CLI entry point."""
    async with AsyncServeEngine(engine, faults=faults, clock=clock,
                                round_time_s=round_time_s) as srv:
        await srv.run_workload(timed)
        return await srv.close()
