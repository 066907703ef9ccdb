"""Reads the program's own per-round series: the engine's span phase
sums (``<phase>_s`` columns, host seconds) and its cumulative counters,
one row per scheduler round, stamped with the round's end (``t``, host
perf_counter seconds).  A program without these columns reads nothing.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def untraced_end(run) -> float:
    """Where host timings stop being read: the trace's start in a traced
    run (the profiler's Python tracer slows the host), else the window's
    close."""
    return run.worklog.started if run.worklog.started is not None \
        else run.window.close


def rows(run, lo: float, hi: float, *columns) -> Optional[dict]:
    """The named columns over the rounds that ended in ``[lo, hi)``, or
    None when a column is missing or no round ended there."""
    ts = run.timeseries
    if "t" not in ts or any(c not in ts for c in columns):
        return None
    inside = (ts["t"] >= lo) & (ts["t"] < hi)
    if not inside.any():
        return None
    return {c: np.asarray(ts[c], float)[inside] for c in columns}


def counted(run, name: str, lo: float, hi: float) -> Optional[float]:
    """How much a cumulative counter grew over the rounds that ended in
    ``[lo, hi)``, or None when the program has no such counter."""
    ts = run.timeseries
    if "t" not in ts or name not in ts:
        return None
    c = np.asarray(ts[name], float)
    before = c[ts["t"] < lo]
    upto = c[ts["t"] < hi]
    if not upto.size:
        return None
    return float(upto[-1] - (before[-1] if before.size else 0.0))


def per_round_counts(run, name: str, lo: float, hi: float):
    """A cumulative counter's growth in each round that ended in
    ``[lo, hi)``, or None when the program has no such counter."""
    ts = run.timeseries
    if "t" not in ts or name not in ts:
        return None
    inc = np.diff(np.asarray(ts[name], float), prepend=0.0)
    return inc[(ts["t"] >= lo) & (ts["t"] < hi)]
