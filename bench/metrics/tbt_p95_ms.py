"""95th percentile of the gap between consecutive tokens of a request,
milliseconds, over all gaps: open loop, every gap of every request due
in the window; closed loop, every gap that ends inside the window."""

from bench.traffic.generate import percentile


def read(run):
    gaps = []
    if run.loop == "open":
        for r in run.counted:
            gaps += [b - a for a, b in zip(r.times, r.times[1:])]
    else:
        lo, hi = run.window.open, run.window.close
        for r in run.records.values():
            gaps += [b - a for a, b in zip(r.times, r.times[1:])
                     if lo <= b < hi]
    return percentile(gaps, 95) * 1e3 if gaps else None
