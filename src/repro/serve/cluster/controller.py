"""Cluster controller: placement, fleet stepping, handoff, and retry.

The controller is the only component that sees every replica.  It owns:

  placement    every fresh request goes through the :class:`Router`
               (load + prefix affinity) to a prefill-capable replica;
               every :class:`HandoffTicket` goes least-loaded to a
               decode-capable one.
  the clock    ``step()`` advances each alive worker one scheduler
               round, in worker-id order — the fleet is deterministic
               because the sweep order is.
  handoff      prefill-role workers return tickets from ``step()``; the
               controller routes and delivers them in the same fleet
               round (disaggregated prefill/decode is two sessions and
               one ``SwapHandle`` apart).
  retry        a replica dying (an exception escaping its round, or an
               injected :meth:`fail_worker`) drains through re-routing:
               the controller re-submits each lost request from its own
               pristine copy to a surviving replica.  Outputs are
               unchanged — ``(uid, position)``-keyed sampling makes the
               re-serve bit-identical — so the client stream just
               resumes where it stopped.
  the ledger   a fleet-level status ledger measured at the routing
               layer (enqueued/first-token/finished in fleet rounds and
               wall seconds, placement, handoffs, reroutes) — what a
               client of the *cluster* experiences, as opposed to the
               per-replica ledgers the workers keep.

Per-request outputs are bit-identical to a single direct engine serve
for any replica count, router policy, disaggregation split, or failure
schedule: every mechanism above moves *where* work runs, and the engine
guarantees outputs do not depend on that.

:class:`AsyncClusterFrontend` wraps a controller in the same
streaming-session shape as :class:`~repro.serve.async_engine
.AsyncServeEngine` — per-request :class:`TokenStream` iterators and an
awaitable backpressure ``submit()`` that holds the request while every
eligible replica is past its queue watermark (instead of letting one
replica shed while another idles).
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax

from repro.serve import sla
from repro.serve.async_engine import TokenStream
from repro.serve.audit import AuditReport, audit_fleet
from repro.serve.engine import (STATUS_OK, Request, ServeEngine,
                                TERMINAL_STATUSES)
from repro.serve.faults import FaultSchedule
from repro.serve.workload import TimedRequest

from repro.serve.cluster.router import Router, route_handoff
from repro.serve.cluster.worker import EngineWorker, HandoffTicket

_DRAIN_GUARD = 100_000


class ClusterController:
    """Own a fleet of :class:`EngineWorker` replicas behind one router."""

    def __init__(self, workers: List[EngineWorker], router: Router, *,
                 catalog_refresh: int = 8):
        if not workers:
            raise ValueError("a cluster needs at least one worker")
        ids = [w.worker_id for w in workers]
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate worker ids: {ids}")
        self.workers: Dict[Any, EngineWorker] = {
            w.worker_id: w for w in workers}
        self.order = ids                     # deterministic sweep order
        self.router = router
        self.catalog_refresh = catalog_refresh
        self._validate_parity(workers)
        self.rnd = 0
        self.t0 = time.perf_counter()
        # fleet ledger: uid -> what the cluster's client experiences
        self.fleet: Dict[int, Dict[str, Any]] = {}
        self.results: Dict[int, List[int]] = {}
        self._origin: Dict[int, Request] = {}    # pristine copies (retry)
        self._current: Dict[int, Request] = {}   # object now serving uid
        self.handoffs = 0
        self.reroutes = 0
        self.last_stats: Dict[Any, Any] = {}
        self.last_pool_stats: Dict[Any, Any] = {}
        self.audit_report: Optional[AuditReport] = None
        self._closed = False

    @staticmethod
    def _validate_parity(workers: List[EngineWorker]):
        """Bit-parity across routing requires every replica to sample
        and cache identically: same sampling seed, temperature, length
        budget, page format.  Catch a mismatched fleet at construction,
        not as a parity-gate failure three layers up."""
        def key(w: EngineWorker):
            e = w.engine
            return (e._seed, e.temperature, e.max_seq, e.page_size,
                    e.kv_dtype, e.spec_k)

        keys = {key(w) for w in workers}
        if len(keys) != 1:
            raise ValueError(
                "replicas disagree on (seed, temperature, max_seq, "
                f"page_size, kv_dtype, spec_k): {sorted(map(str, keys))} "
                "— outputs would depend on placement")

    # ------------------------------------------------------------ placement
    def _stats(self) -> Dict[Any, Any]:
        return {wid: w.stats() for wid, w in self.workers.items()
                if w.alive}

    def _prefill_capable(self) -> List[Any]:
        return [wid for wid in self.order
                if self.workers[wid].alive
                and self.workers[wid].role in ("prefill", "mixed")]

    def submit(self, req: Request):
        """Route a fresh request to a replica and record it in the
        fleet ledger."""
        self._require_open()
        if req.uid in self.fleet:
            raise ValueError(f"duplicate request uid {req.uid}")
        wid = self.router.route(req, self._stats(),
                                eligible=self._prefill_capable())
        self.fleet[req.uid] = {
            "status": None, "worker": wid, "enqueued_round": self.rnd,
            "enqueued_s": time.perf_counter() - self.t0,
            "handoffs": 0, "reroutes": 0,
        }
        self._origin[req.uid] = dataclasses.replace(req, generated=None)
        self._current[req.uid] = req
        self.workers[wid].submit(req)

    # ------------------------------------------------------------- stepping
    def step(self):
        """One fleet round: every alive worker steps once (id order),
        handoff tickets route and deliver, terminal statuses and first
        tokens land in the fleet ledger, and the prefix catalog
        refreshes from the replicas' advertised keys."""
        self._require_open()
        self.rnd += 1
        for wid in self.order:
            w = self.workers[wid]
            if not w.alive or not (w.has_work or w.lost):
                continue
            try:
                tickets = w.step()
            except Exception as exc:   # noqa: BLE001 — replica death
                self._handle_death(wid, exc)
                continue
            for ticket in tickets:
                self._deliver_handoff(ticket)
        # catalog refresh sits in the overlap gap: with pipelined
        # workers every replica's decode step is still in flight here,
        # so walking the prefix indexes (host-side radix state the
        # in-flight step never edits) rides under the device work
        if self.catalog_refresh and self.rnd % self.catalog_refresh == 0:
            for wid in self.order:
                w = self.workers[wid]
                if w.alive:
                    self.router.advertise(wid, w.prefix_keys())
        for wid in self.order:
            if self.workers[wid].alive:
                self._collect(wid)
        self._watch_first_tokens()

    def _deliver_handoff(self, ticket: HandoffTicket):
        wid = route_handoff(self.order, self._stats())
        self.workers[wid].submit_handoff(ticket)
        entry = self.fleet[ticket.uid]
        entry["worker"] = wid
        entry["handoffs"] += 1
        self._current[ticket.uid] = ticket.request
        self.handoffs += 1

    def _collect(self, wid):
        for uid, status, tokens, reason in self.workers[wid].poll():
            self._record_terminal(uid, status, tokens, reason, wid)

    def _record_terminal(self, uid, status, tokens, reason, wid):
        entry = self.fleet.get(uid)
        if entry is None or entry["status"] is not None:
            return
        entry["status"] = status
        entry["finished_round"] = self.rnd
        entry["finished_s"] = time.perf_counter() - self.t0
        entry["worker"] = wid
        if reason:
            entry["reason"] = reason
        if status == STATUS_OK and tokens is not None:
            self.results[uid] = tokens
            entry["tokens"] = len(tokens)
        else:
            entry["tokens"] = 0

    def _watch_first_tokens(self):
        for uid, entry in self.fleet.items():
            if "first_token_round" in entry:
                continue
            req = self._current.get(uid)
            if req is not None and req.generated:
                entry["first_token_round"] = self.rnd
                entry["first_token_s"] = time.perf_counter() - self.t0

    # -------------------------------------------------------------- failure
    def fail_worker(self, wid, exc: Optional[BaseException] = None):
        """Kill a replica mid-serve (chaos injection): its in-flight
        requests drain through the retry path onto survivors."""
        self._require_open()
        w = self.workers[wid]
        if not w.alive:
            return
        w.fail(exc)
        self._handle_death(wid, exc)

    def _handle_death(self, wid, exc):
        """A replica died: accept the terminal statuses it reached
        before dying, then re-route everything it lost from the
        controller's pristine copies.  The re-serve replays the same
        tokens (uid-keyed sampling), so the client never notices beyond
        latency."""
        w = self.workers[wid]
        lost = set(w.lost)
        for uid, status, tokens, reason in w.poll():
            if uid not in lost:
                self._record_terminal(uid, status, tokens, reason, wid)
        stats = self._stats()
        if not stats:
            raise RuntimeError(
                f"worker {wid} died and no replica survives") from exc
        for uid in w.lost:
            entry = self.fleet.get(uid)
            if entry is None or entry["status"] is not None:
                continue
            fresh = dataclasses.replace(self._origin[uid], generated=None)
            target = self.router.route(fresh, stats,
                                       eligible=self._prefill_capable())
            entry["worker"] = target
            entry["reroutes"] += 1
            self.reroutes += 1
            self._current[uid] = fresh
            self.workers[target].submit(fresh)

    # ------------------------------------------------------------- draining
    @property
    def pending(self) -> List[int]:
        return [uid for uid, e in self.fleet.items()
                if e["status"] is None]

    def drain(self):
        """Step until every fleet request is terminal."""
        guard = 0
        while self.pending:
            self.step()
            guard += 1
            if guard > _DRAIN_GUARD:
                raise RuntimeError(
                    f"cluster failed to drain: {self.pending} still "
                    f"pending after {guard} rounds")

    def serve(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Closed-loop convenience mirroring ``ServeEngine.serve``:
        submit everything, drain, close; returns {uid: tokens} for OK
        requests (fleet stats in ``last_stats``)."""
        for req in requests:
            self.submit(req)
        self.drain()
        return self.close()

    def run_workload(self, timed: List[TimedRequest],
                     round_time_s: float = 1.0) -> Dict[int, List[int]]:
        """Replay an arrival process on the fleet round clock: a request
        whose arrival maps to round r is routed before round r runs.
        Deterministic — same workload, same fleet, same placements."""
        self._require_open()
        arrivals = deque(sorted(timed, key=lambda t: t.arrival_s))
        guard = 0
        while arrivals or self.pending:
            while arrivals and (int(arrivals[0].arrival_s / round_time_s)
                                <= self.rnd):
                self.submit(arrivals.popleft().request)
            self.step()
            guard += 1
            if guard > _DRAIN_GUARD:
                raise RuntimeError("cluster failed to drain the workload")
        return dict(self.results)

    # -------------------------------------------------------------- closing
    def close(self) -> Dict[int, List[int]]:
        """Finalize every surviving replica session, assemble fleet
        stats (``last_stats`` with the fleet ledger + SLA + router
        figures, ``last_pool_stats`` per replica) and run the fleet
        audit (``audit_report``).  Idempotent."""
        if self._closed:
            return dict(self.results)
        missing = [uid for uid, e in self.fleet.items()
                   if e["status"] not in TERMINAL_STATUSES]
        if missing:   # fleet statuses partition the request set, always
            raise RuntimeError(
                f"cluster requests without a terminal status: {missing}")
        per_worker = {wid: dict(w.ledger)
                      for wid, w in self.workers.items()}
        tbt = [t for wid in self.order for t in self.workers[wid].tbt]
        self.last_stats = dict(self.fleet)
        self.last_stats["sla"] = sla.fleet_summary(
            per_worker, tbt_s=tbt,
            wall_s=time.perf_counter() - self.t0)
        self.last_stats["router"] = {
            "policy": self.router.policy,
            "decisions": {str(k): v
                          for k, v in self.router.decisions.items()},
            "affinity_hits": self.router.affinity_hits,
            "handoffs": self.handoffs,
            "reroutes": self.reroutes,
            "rounds": self.rnd,
        }
        for wid in self.order:
            w = self.workers[wid]
            if w.alive:
                w.finalize()
        self.last_pool_stats = {
            wid: w.manager.stats() for wid, w in self.workers.items()
            if w.manager is not None}
        self.audit_report = audit_fleet(
            {wid: w.manager for wid, w in self.workers.items()})
        self._closed = True
        return dict(self.results)

    def _require_open(self):
        if self._closed:
            raise RuntimeError("cluster controller already closed")


class AsyncClusterFrontend:
    """Streaming front-end over a :class:`ClusterController`, in the
    :class:`AsyncServeEngine` shape: ``submit()`` returns a
    :class:`TokenStream`, the controller steps on the event loop, and
    (with ``backpressure_watermark``) submission awaits while *every*
    prefill-capable replica's queue is at/above the watermark — the
    fleet-level version of the single-engine awaitable backpressure,
    holding the request until some replica has room instead of letting
    the routed one shed it."""

    def __init__(self, controller: ClusterController, *,
                 backpressure_watermark: Optional[int] = None,
                 idle_poll_s: float = 0.002):
        self.controller = controller
        self.backpressure_watermark = backpressure_watermark
        self.idle_poll_s = idle_poll_s
        self._streams: Dict[int, TokenStream] = {}
        self._open: set = set()
        self._task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        self._round_evt = asyncio.Event()
        self._closing = False
        self._error: Optional[BaseException] = None

    async def __aenter__(self) -> "AsyncClusterFrontend":
        self._ensure_started()
        return self

    async def __aexit__(self, exc_type, exc, tb):
        if exc_type is None:
            await self.close()
        else:
            self._closing = True
            self._wake.set()

    def _ensure_started(self):
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._run())

    # ------------------------------------------------------------- requests
    async def submit(self, request: Request) -> TokenStream:
        self._ensure_started()
        self._check_live()
        if self.backpressure_watermark is not None:
            while self._saturated():
                self._round_evt.clear()
                self._wake.set()
                await self._round_evt.wait()
                self._check_live()
        stream = TokenStream(request.uid)
        self._streams[request.uid] = stream
        self._open.add(request.uid)
        self.controller.submit(request)
        self._wake.set()
        return stream

    def _saturated(self) -> bool:
        c = self.controller
        depths = [c.workers[wid].stats().queue_depth
                  for wid in c._prefill_capable()]
        return bool(depths) and min(depths) >= self.backpressure_watermark

    def _check_live(self):
        if self._error is not None:
            raise RuntimeError("cluster session already failed") \
                from self._error
        if self._closing:
            raise RuntimeError("cluster session is closing")

    async def close(self) -> Dict[int, List[int]]:
        if self._task is None:
            return {}
        self._closing = True
        self._wake.set()
        await self._task
        if self._error is not None:
            raise self._error
        return self.controller.close()

    # ------------------------------------------------------------- the loop
    async def _run(self):
        c = self.controller
        try:
            while True:
                if not c.pending:
                    if self._closing:
                        break
                    await self._idle_wait()
                    if not c.pending:
                        continue
                c.step()
                self._publish()
                self._round_evt.set()
                await asyncio.sleep(0)
        except BaseException as exc:   # noqa: BLE001 — reported via close()
            self._error = exc
            for uid in list(self._open):
                self._streams[uid]._fail(exc)
                self._open.discard(uid)
        finally:
            self._round_evt.set()

    async def _idle_wait(self):
        self._wake.clear()
        try:
            await asyncio.wait_for(self._wake.wait(), self.idle_poll_s)
        except asyncio.TimeoutError:
            pass

    def _publish(self):
        c = self.controller
        for uid in list(self._open):
            stream = self._streams[uid]
            entry = c.fleet.get(uid)
            if entry is None:
                continue
            status = entry["status"]
            if status is None or status == STATUS_OK:
                req = c._current.get(uid)
                gen = (req.generated or []) if req is not None else []
                while stream._sent < len(gen):
                    stream._push(gen[stream._sent])
                    stream._sent += 1
            if status is not None:
                stream._close(status, entry.get("reason"))
                self._open.discard(uid)


def make_cluster(model, params, *, replicas: int = 2,
                 router_policy: str = "cache-aware",
                 disaggregate: bool = False, prefill_workers: int = 1,
                 share_engine: bool = True, faults_seed: Optional[int] = None,
                 worker_faults: Optional[Dict[Any, Any]] = None,
                 catalog_refresh: int = 8,
                 **engine_kw) -> ClusterController:
    """Build a fleet: ``replicas`` workers over identically-configured
    paged engines, a router with the given policy, and a controller.

    Worker i runs on ``jax.devices()[i % n]`` — one replica per chip on a
    multi-chip host — with its own copy of ``params`` committed there.
    Workers on the same device share one engine object by default —
    sessions are independent, and sharing reuses the jit caches instead
    of compiling per replica; ``share_engine=False`` gives every worker
    its own.

    ``disaggregate=True`` splits roles: the first ``prefill_workers``
    replicas only prefill (their sessions never decode) and the rest
    only decode, joined by SwapHandle handoff.  ``faults_seed`` derives
    an independent deterministic chaos schedule per worker via
    :meth:`FaultSchedule.random_for_worker`; ``worker_faults`` maps
    worker id -> schedule for hand-built chaos."""
    if replicas < 1:
        raise ValueError(f"need >= 1 replica; got {replicas}")
    if disaggregate and replicas < 2:
        raise ValueError("disaggregation needs >= 2 replicas (at least "
                         "one prefill and one decode)")
    if disaggregate and not 1 <= prefill_workers < replicas:
        raise ValueError(f"prefill_workers must be in [1, {replicas - 1}]; "
                         f"got {prefill_workers}")
    engine_kw.setdefault("cache_layout", "paged")
    devices = jax.devices()
    placed: Dict[Any, Any] = {}       # device -> params committed there
    engines: Dict[Any, ServeEngine] = {}
    workers = []
    for i in range(replicas):
        device = devices[i % len(devices)]
        if device not in placed:
            placed[device] = jax.device_put(params, device)
        key = device if share_engine else i
        if key not in engines:
            engines[key] = ServeEngine(model, placed[device], **engine_kw)
        if disaggregate:
            role = "prefill" if i < prefill_workers else "decode"
        else:
            role = "mixed"
        faults = None
        if worker_faults is not None:
            faults = worker_faults.get(i)
        elif faults_seed is not None:
            faults = FaultSchedule.random_for_worker(faults_seed, i)
        workers.append(EngineWorker(i, engines[key], role=role,
                                    faults=faults))
    router = Router([w.worker_id for w in workers], policy=router_policy,
                    page_size=workers[0].engine.page_size)
    return ClusterController(workers, router,
                             catalog_refresh=catalog_refresh)
