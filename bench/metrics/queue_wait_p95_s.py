"""Scheduler: 95th percentile of the wait from a request's due time to
its admission (the engine ledger's ``admitted_s``), seconds, over the
requests due in the window that were admitted.  In a traced run only
those due before the trace began count, so that the profiler's own
start and stop do not show as queueing."""

from bench.traffic.generate import percentile


def read(run):
    started = run.worklog.started
    cut = started if started is not None else float("inf")
    waits = [r.admitted - r.due for r in run.counted
             if r.admitted is not None and r.due < cut]
    return percentile(waits, 95) if waits else None
