"""Reduces a profiler trace (``.xplane.pb``) to the numbers the metrics
read.

The trace has one plane per device (``/device:TPU:<n>``) with a line of
jitted programs (``XLA Modules``) and a line of their operations
(``XLA Ops``, Pallas kernels among them as custom calls), and host planes
whose Python thread's line holds the profiler annotations
(``bench.window`` bounds the traced window; ``engine.*`` name the
scheduler's phases).

* busy: per device, the union of its operations' intervals inside the
  window; averaged over the devices.
* programs / ops: per name, the number of events that start inside the
  window and their summed device seconds (an operation's trailing
  ``.<n>`` is dropped so that one kernel's calls in different layers add
  up).
* idle gaps: the device's idle intervals inside the window, each put to
  the innermost host annotation that covers its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                                  # mean over devices
    programs: Dict[str, List[float]]               # name -> [count, s]
    ops: Dict[str, List[float]]                    # name -> [count, s]
    idle_by_host: Dict[str, float]                 # label -> idle s

    def program_seconds(self, part: str) -> Tuple[int, float]:
        """Events and device seconds of programs whose name holds
        ``part``."""
        n = s = 0.0
        for name, (c, t) in self.programs.items():
            if part in name:
                n, s = n + c, s + t
        return int(n), s

    def op_seconds(self, parts) -> Tuple[int, float]:
        """Events and device seconds of operations whose name holds any
        of ``parts``."""
        n = s = 0.0
        for name, (c, t) in self.ops.items():
            if any(p in name for p in parts):
                n, s = n + c, s + t
        return int(n), s

    def top_ops(self, k: int = 10):
        return sorted(([n, v[1]] for n, v in self.ops.items()),
                      key=lambda x: -x[1])[:k]

    def top_idle(self, k: int = 10):
        return sorted(([n, v] for n, v in self.idle_by_host.items()),
                      key=lambda x: -x[1])[:k]


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_events(path: str) -> List[Event]:
    """Every event of the device planes and of the host planes' lines."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return events_of(pd)


def events_of(pd) -> List[Event]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(("/device:", "/host:")):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


_SUFFIX = re.compile(r"\.\d+$")


def _union(intervals) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _label_gaps(gaps, host: List[Event]) -> Dict[str, float]:
    """Idle seconds per innermost host annotation covering each gap's
    midpoint (a sweep over both, sorted by time)."""
    host = sorted(host, key=lambda e: e.start_ns)
    idle: Dict[str, float] = defaultdict(float)
    active: List[Event] = []
    i = 0
    for a, b in sorted(gaps):
        mid = (a + b) / 2
        while i < len(host) and host[i].start_ns <= mid:
            active.append(host[i])
            i += 1
        active = [e for e in active if e.end_ns > mid]
        label = (min(active, key=lambda e: e.dur_ns).name if active
                 else "host:outside engine calls")
        idle[label] += (b - a) * 1e-9
    return dict(idle)


def reduce(events: List[Event]) -> Optional[Reduced]:
    """None when the trace holds no window or no device operation.  The
    host annotations are those of the thread (line) that opened the
    window: the event loop's, which runs the engine's rounds."""
    windows = [e for e in events if not e.plane.startswith("/device:")
               and e.name == WINDOW]
    if not windows:
        return None
    host_line = (windows[0].plane, windows[0].line)
    t0 = min(e.start_ns for e in windows)
    t1 = max(e.end_ns for e in windows)
    ops_by_dev = defaultdict(list)
    programs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    ops: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for e in events:
        if not e.plane.startswith("/device:"):
            continue
        if e.line == OPS_LINE:
            a, b = max(e.start_ns, t0), min(e.end_ns, t1)
            if b > a:
                ops_by_dev[e.plane].append((a, b))
        if not t0 <= e.start_ns < t1:
            continue    # counted whole, where it starts
        if e.line == MODULES_LINE:
            p = programs[e.name]
        elif e.line == OPS_LINE:
            p = ops[_SUFFIX.sub("", e.name)]
        else:
            continue
        p[0] += 1
        p[1] += e.dur_ns * 1e-9
    if not ops_by_dev:
        return None
    busy, gaps = [], []
    for plane, iv in sorted(ops_by_dev.items()):
        u = _union(iv)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        if not gaps:    # gaps of the first device name the host's work
            edges = [t0] + [x for ab in u for x in ab] + [t1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    idle = _label_gaps(gaps, [e for e in events
                              if (e.plane, e.line) == host_line
                              and e.name != WINDOW])
    return Reduced(window_s=(t1 - t0) * 1e-9,
                   busy_s=sum(busy) / len(busy),
                   programs=dict(programs), ops=dict(ops),
                   idle_by_host=dict(idle))
