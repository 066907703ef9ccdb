"""Spans of the serving engine's host phases.

``span(st, name, **meta)`` times one phase of a scheduler round.  It
enters a ``jax.profiler.TraceAnnotation``, so that while a profile is
being taken the phase lands on the host line of the profiler's own
trace, on the same clock as the device's operations (with no profiler
running that costs a flag check).  It also adds the phase's host-clock
seconds to ``st.phase_s``, the round's phase sums, which the engine
writes as the per-round timeseries columns ``<phase>_s`` (``SPANS``
minus the ``serve.`` prefix).  Sums are inclusive: a span's seconds
count in its own column and in every enclosing span's.

Spans are per phase, never per slot or per token.  Every blocking
device-to-host transfer of the serving path sits in one of the three
``*fetch`` spans, so host work and waiting on the device can be told
apart.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

SPANS = (
    "serve.round",          # one whole scheduler round
    "serve.sweep",          # fault clock, expiry/cancel, admission control
    "serve.fetch",          # the decode commit's device_get
    "serve.commit",         # token and terminal accounting after the fetch
    "serve.admit",          # admission, prefills included
    "serve.prefix_plan",    # radix walk of a prompt (plan_admit)
    "serve.prefill",        # dispatch of one prefill program
    "serve.scatter",        # prefill K/V into pages; copy-on-write forks
    "serve.prefill_fetch",  # first-token device_get after a prefill
    "serve.grow",           # page growth and preemption
    "serve.tables",         # block-table upload when the allocator changed
    "serve.dispatch",       # issue of one decode step
    "serve.swap_fetch",     # swap-out pages copied to host
    "serve.ingest",         # open-loop arrivals taken into the queue
    "serve.publish",        # committed tokens pushed to client streams
)


def new_phases() -> dict:
    """Zeroed phase sums, one per span name."""
    return dict.fromkeys(SPANS, 0.0)


def column(name: str) -> str:
    """The timeseries column of a span: ``serve.fetch`` -> ``fetch_s``."""
    return name[len("serve."):] + "_s"


class span:
    """Context manager: a profiler annotation plus the phase's seconds
    added to ``st.phase_s[name]`` (a name outside ``SPANS`` raises)."""

    __slots__ = ("_st", "_name", "_ann", "_t")

    def __init__(self, st, name: str, **meta):
        self._st, self._name = st, name
        self._ann = TraceAnnotation(name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t = time.perf_counter()

    def __exit__(self, *exc):
        # looked up at exit: the round's sums may be reset meanwhile
        self._st.phase_s[self._name] += time.perf_counter() - self._t
        self._ann.__exit__(*exc)
