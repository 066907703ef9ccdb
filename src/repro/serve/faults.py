"""Deterministic fault injection for the serving stack.

The paper's HW-vs-SW result is that a software path can stand in for a
hardware feature when the hardware path is unavailable — our serving
engine carries the same pairs (Pallas kernel vs chunked-``jnp``
attention, paged vs dense cache, speculative vs plain decode), but a
fallback is only real if it can be *exercised*.  This module makes every
failure mode the engine claims to survive injectable, deterministically,
at a chosen scheduler round:

  oom               the page allocator reports exhaustion even though
                    pages are free — drives the admission gate, growth
                    preemption, and (``raise_exc=True``) the step-restart
                    recovery path
  nan               a request's logits turn NaN inside the fused step —
                    drives the NaN-guard quarantine (only the targeted
                    request fails, the batch survives)
  straggler         a decode step stalls for ``sleep_s`` wall seconds —
                    drives the serve-loop watchdog
  spec_collapse     a request's draft proposals are perturbed so the
                    verify step rejects them — drives the per-request
                    speculative auto-disable / cooldown policy
  page_corruption   a live physical page is overwritten with NaN —
                    drives the guard end-to-end (corruption surfaces as
                    NaN logits in whoever reads the page)
  kernel            the kernel-backend dispatch raises — drives the
                    graceful kernel -> chunked-jnp SW degradation (the
                    paper's HW->SW story as a runtime policy)
  cancel            the request is cancelled at that round — drives the
                    cancellation path without needing a second thread
  deadline          the request's deadline is treated as expired at that
                    round — deterministic TIMEOUT (wall-clock deadlines
                    work too, but cannot be asserted bit-for-bit)

Faults are keyed on the engine's *scheduler round* — a counter that
advances once per admission+step cycle whether or not a decode step ran,
so a fault window always expires even when the engine is spinning on a
blocked admission gate.  A :class:`FaultSchedule` is a pure function of
``(kind, round)``: replaying the same schedule against the same requests
produces the same injections, which is what lets the tests assert that
every surviving request's output is bit-identical to the fault-free run.

Everything here is a no-op by default: an engine with ``faults=None``
never calls into this module from its hot loop.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

FAULT_KINDS = ("oom", "nan", "straggler", "spec_collapse",
               "page_corruption", "kernel", "cancel", "deadline")


def fold_worker_seed(seed: int, worker_id) -> int:
    """Fold a worker id into a fault seed, deterministically and
    platform-stably (no ``hash()`` — string hashing is randomized per
    process, and two replicas of a cluster must derive the *same*
    schedule for the same worker across runs).

    Without this, every replica of a fleet built from one ``--fault-seed``
    would replay the *same* schedule — synchronized corruption on every
    replica at the same round, which is chaos aliasing, not chaos."""
    h = hashlib.blake2b(f"{int(seed)}|{worker_id}".encode(), digest_size=8)
    return int.from_bytes(h.digest(), "little") % (2 ** 31)


class InjectedFault(RuntimeError):
    """An injected failure surfacing as an exception.

    ``fatal=True`` marks it unrecoverable: the engine's step-restart
    recovery must let it propagate (the exception-safety tests ride on
    this), releasing every live slot and page on the way out.
    """

    def __init__(self, msg: str, *, fatal: bool = False):
        super().__init__(msg)
        self.fatal = fatal


class KernelBackendError(InjectedFault):
    """An injected kernel-backend dispatch failure.

    The engine reacts by rebuilding its step functions on the chunked-jnp
    SW path and replaying the interrupted step — requests never observe
    the failure beyond latency.
    """


@dataclasses.dataclass(frozen=True)
class Fault:
    """One injected failure.

    ``step`` is the scheduler round the fault first fires at; ``span``
    rounds keep window faults (oom / nan / straggler / spec_collapse)
    active, while point faults (cancel / deadline / kernel /
    page_corruption and ``raise_exc`` ooms) fire exactly once, at
    ``step``.  ``uid`` targets one request where that makes sense
    (nan / spec_collapse / cancel / deadline); ``None`` hits every live
    request.
    """
    kind: str
    step: int
    uid: Optional[int] = None
    span: int = 1
    page: Optional[int] = None      # page_corruption target (None: seeded)
    sleep_s: float = 0.05           # straggler stall
    raise_exc: bool = False         # oom: raise instead of soft-denying
    fatal: bool = False             # raised faults: unrecoverable

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"pick from {FAULT_KINDS}")
        if self.step < 0 or self.span < 1:
            raise ValueError(f"fault needs step >= 0, span >= 1; "
                             f"got step={self.step} span={self.span}")

    def active_at(self, rnd: int) -> bool:
        return self.step <= rnd < self.step + self.span


class FaultSchedule:
    """A deterministic set of faults, queried by (kind, round).

    Stateless by design: the schedule never remembers what fired, so the
    same schedule object can be replayed across ``serve()`` calls (the
    engine's round counter restarts per call and point faults re-fire at
    the same rounds — exactly what a regression test wants).
    """

    def __init__(self, faults: Iterable[Fault] = (), seed: int = 0):
        self.faults: List[Fault] = list(faults)
        self.seed = seed

    def __len__(self) -> int:
        return len(self.faults)

    def __repr__(self) -> str:
        return f"FaultSchedule(seed={self.seed}, faults={self.faults!r})"

    # ------------------------------------------------------------- queries
    def active(self, kind: str, rnd: int) -> List[Fault]:
        return [f for f in self.faults
                if f.kind == kind and f.active_at(rnd)]

    def oom_denied(self, rnd: int) -> bool:
        """Soft OOM: the allocator pretends exhaustion this round."""
        return any(not f.raise_exc for f in self.active("oom", rnd))

    def oom_raise(self, rnd: int) -> Optional[Fault]:
        """Hard OOM: the allocator raises (fires only at ``step``)."""
        for f in self.faults:
            if f.kind == "oom" and f.raise_exc and f.step == rnd:
                return f
        return None

    def kernel_at(self, rnd: int) -> Optional[Fault]:
        for f in self.faults:
            if f.kind == "kernel" and f.step == rnd:
                return f
        return None

    def straggler_sleep(self, rnd: int) -> float:
        return sum(f.sleep_s for f in self.active("straggler", rnd))

    def nan_uids(self, rnd: int) -> List[Optional[int]]:
        return [f.uid for f in self.active("nan", rnd)]

    def collapse_uids(self, rnd: int) -> List[Optional[int]]:
        return [f.uid for f in self.active("spec_collapse", rnd)]

    def cancels_at(self, rnd: int) -> List[int]:
        return [f.uid for f in self.faults
                if f.kind == "cancel" and f.step == rnd
                and f.uid is not None]

    def deadline_expiries_at(self, rnd: int) -> List[int]:
        return [f.uid for f in self.faults
                if f.kind == "deadline" and f.step == rnd
                and f.uid is not None]

    def corruptions_at(self, rnd: int) -> List[Fault]:
        return [f for f in self.faults
                if f.kind == "page_corruption" and f.step == rnd]

    def corruption_target(self, fault: Fault, rnd: int,
                          mapped_pages: Sequence[int]) -> Optional[int]:
        """Resolve a corruption fault to a physical page: the explicit
        target if given, else a seeded choice among the live mapped
        pages (None when nothing is mapped)."""
        if fault.page is not None:
            return fault.page
        if not mapped_pages:
            return None
        rng = np.random.default_rng((self.seed, rnd))
        return int(sorted(mapped_pages)[rng.integers(len(mapped_pages))])

    # ------------------------------------------------------ worker scoping
    def scoped(self, worker_id) -> "FaultSchedule":
        """The same fault list, re-seeded for one cluster worker: seeded
        choices (the page-corruption target) stop aliasing across
        replicas while the hand-written rounds/kinds stay put.  Use
        :meth:`random_for_worker` when each replica should draw an
        independent schedule."""
        return FaultSchedule(self.faults,
                             seed=fold_worker_seed(self.seed, worker_id))

    @classmethod
    def random_for_worker(cls, seed: int, worker_id, *,
                          n_faults: int = 4, max_step: int = 24,
                          uids: Sequence[int] = (),
                          kinds: Sequence[str] = FAULT_KINDS,
                          ) -> "FaultSchedule":
        """A seeded random schedule independent per worker: one fleet
        ``seed`` fans out to per-replica schedules via
        :func:`fold_worker_seed`, so replica 0's OOM burst does not
        replay simultaneously on every replica — while each worker's own
        schedule stays exactly reproducible from ``(seed, worker_id)``."""
        return cls.random(fold_worker_seed(seed, worker_id),
                          n_faults=n_faults, max_step=max_step,
                          uids=uids, kinds=kinds)

    # ---------------------------------------------------------- generation
    @classmethod
    def random(cls, seed: int, *, n_faults: int = 4, max_step: int = 24,
               uids: Sequence[int] = (), kinds: Sequence[str] = FAULT_KINDS,
               ) -> "FaultSchedule":
        """A seeded random schedule over ``kinds``: the benchmark's and
        the property tests' workhorse.  Raised-OOM faults are generated
        non-fatal (the engine recovers by step restart); fatal faults are
        for the targeted exception-safety tests, not the random sweep."""
        rng = np.random.default_rng(seed)
        faults = []
        for _ in range(int(rng.integers(1, n_faults + 1))):
            kind = kinds[int(rng.integers(len(kinds)))]
            step = int(rng.integers(0, max_step))
            uid = (int(rng.choice(list(uids)))
                   if len(uids) and kind in ("nan", "spec_collapse",
                                             "cancel", "deadline")
                   else None)
            faults.append(Fault(
                kind=kind, step=step, uid=uid,
                span=int(rng.integers(1, 4)),
                sleep_s=float(rng.uniform(0.01, 0.04)),
                raise_exc=bool(kind == "oom" and rng.integers(2))))
        return cls(faults, seed=seed)


@functools.partial(jax.jit, donate_argnums=(0,))
def poison_pages(pool, page_idx: jnp.ndarray):
    """Overwrite physical pages ``page_idx`` ((n,) int32) with NaN across
    every leaf of the donated pool — the page-corruption injection.
    Whoever reads the page next sees NaN attention scores, hence NaN
    logits, hence the engine's quarantine path.

    Generic over pool leaves on purpose: int8 value pages cannot hold a
    NaN (the float->int convert is a harmless defined cast), but their
    float32 ``k_scales``/``v_scales`` rows can — poisoning every leaf
    makes the corruption surface through the fused dequant exactly like
    it does through float pages."""
    poison = jnp.asarray(jnp.nan, jnp.float32)
    out = dict(pool)
    for name, leaf in pool.items():
        out[name] = leaf.at[:, page_idx].set(poison.astype(leaf.dtype))
    return out
