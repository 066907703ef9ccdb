"""Reduces the program's ``serve.*`` spans in a profiler trace: what each
span took, what it took itself, and where the device sat idle.

The spans are those of the thread that opened the traced window
(``bench.window``), the event loop's, clipped to the window.  They nest:
each span's self-time is its duration less its children's.  The device's
idle intervals (the first device, as ``trace_reduce.reduce`` takes them)
are cut by the spans' self-intervals, so each idle nanosecond goes to the
innermost span over it, or to ``OUTSIDE``, and the parts sum to the
device's idle time.  ``fetch_idle_share`` is the part inside the three
``*fetch`` spans, over the window.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import OPS_LINE, WINDOW, Event, _union

PREFIX = "serve."
OUTSIDE = "outside program spans"
FETCH = ("serve.fetch", "serve.prefill_fetch", "serve.swap_fetch")


@dataclasses.dataclass
class SpanReduced:
    window_s: float
    idle_s: float                                  # first device
    spans: Dict[str, List[float]]    # name -> [count, seconds, self seconds]
    idle_by_span: Dict[str, float]   # innermost span (or OUTSIDE) -> idle s

    def fetch_idle_share(self) -> float:
        return sum(self.idle_by_span.get(n, 0.0) for n in FETCH) \
            / self.window_s


def _self_intervals(spans: List[Tuple[float, float, str]]):
    """(start, end, name) of every span's own time, its children's cut
    out: disjoint pieces in time order.  Spans of one thread nest."""
    out: List[Tuple[float, float, str]] = []
    stack: List[List] = []            # [end, name, cursor]

    def close_until(t):
        while stack and stack[-1][0] <= t:
            end, name, cur = stack.pop()
            if end > cur:
                out.append((cur, end, name))
            if stack:
                stack[-1][2] = end

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(a)
        if stack:
            parent = stack[-1]
            if a > parent[2]:
                out.append((parent[2], a, parent[1]))
        stack.append([b, name, a])
    close_until(float("inf"))
    return sorted(out)


def reduce_spans(events: List[Event]) -> Optional[SpanReduced]:
    """None when the trace has no window, no device operation or no
    program span."""
    windows = [e for e in events if not e.plane.startswith("/device:")
               and e.name == WINDOW]
    if not windows:
        return None
    line = (windows[0].plane, windows[0].line)
    t0 = min(e.start_ns for e in windows)
    t1 = max(e.end_ns for e in windows)
    spans = [(max(e.start_ns, t0), min(e.end_ns, t1), e.name)
             for e in events if (e.plane, e.line) == line
             and e.name.startswith(PREFIX)
             and e.end_ns > t0 and e.start_ns < t1]
    devices = sorted({e.plane for e in events
                      if e.plane.startswith("/device:")
                      and e.line == OPS_LINE})
    if not spans or not devices:
        return None
    busy = _union((max(e.start_ns, t0), min(e.end_ns, t1)) for e in events
                  if e.plane == devices[0] and e.line == OPS_LINE
                  and e.end_ns > t0 and e.start_ns < t1)
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]

    table: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0, 0.0])
    for a, b, name in spans:
        table[name][0] += 1
        table[name][1] += (b - a) * 1e-9
    pieces = _self_intervals(spans)
    for a, b, name in pieces:
        table[name][2] += (b - a) * 1e-9

    idle: Dict[str, float] = defaultdict(float)
    total = sum(b - a for a, b in gaps) * 1e-9
    j = 0
    for ga, gb in gaps:
        while j < len(pieces) and pieces[j][1] <= ga:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < gb:
            a, b, name = pieces[k]
            idle[name] += (min(b, gb) - max(a, ga)) * 1e-9
            k += 1
    idle[OUTSIDE] = total - sum(idle.values())
    return SpanReduced(window_s=(t1 - t0) * 1e-9, idle_s=total,
                       spans=dict(table), idle_by_span=dict(idle))
