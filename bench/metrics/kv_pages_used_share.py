"""KV cache: share of the usable page pool not free, mean over the
rounds inside the window (pages held by live requests and by cached
prefixes alike)."""

import numpy as np


def read(run):
    ts = run.timeseries
    if "free_pages" not in ts or not len(ts["free_pages"]):
        return None
    inside = (ts["t"] >= run.window.open) & (ts["t"] < run.window.close)
    free = ts["free_pages"][inside[:len(ts["free_pages"])]]
    if not free.size:
        return None
    return float(np.mean(1.0 - free / (run.num_pages - 1)))
