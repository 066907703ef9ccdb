"""Scheduler: mean live slots per decode round inside the window (the
engine's per-round ``live_slots``, rounds that ran a step)."""

import numpy as np


def read(run):
    ts = run.timeseries
    if "t" not in ts:
        return None
    inside = (ts["t"] >= run.window.open) & (ts["t"] < run.window.close)
    live = ts["live_slots"][inside]
    live = live[live > 0]
    return float(np.mean(live)) if live.size else None
