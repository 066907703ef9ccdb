"""95th percentile of the time to first token, seconds, from each
request's due time (not its ingest) over every request due in the
window.  A request that never delivered a token counts with the time it
waited until the harness gave up on it, a reading no better than its
own; an open-loop run only."""

from bench.traffic.generate import percentile


def read(run):
    if run.loop != "open" or not run.counted:
        return None
    ttft = [(r.times[0] if r.times else run.window.end) - r.due
            for r in run.counted]
    return percentile(ttft, 95)
