"""Output tokens delivered to clients inside the window, by any request,
over the window's length."""


def read(run):
    lo, hi = run.window.open, run.window.close
    n = sum(1 for r in run.records.values() for t in r.times if lo <= t < hi)
    return n / (hi - lo)
