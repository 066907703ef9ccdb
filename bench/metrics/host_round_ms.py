"""Scheduler: host milliseconds per scheduler round that are not spent
blocked on the device.  Read from the program's own spans (per-round
phase sums): ``serve.round`` plus the open-loop ``serve.ingest`` and
``serve.publish`` between rounds, less the three ``*fetch`` spans, over
the rounds that ended inside the window before the trace began (the
profiler's Python tracer slows the host)."""

from bench import rounds

HOST = ("round_s", "ingest_s", "publish_s")
FETCH = ("fetch_s", "prefill_fetch_s", "swap_fetch_s")


def read(run):
    r = rounds.rows(run, run.window.open, rounds.untraced_end(run),
                    *HOST, *FETCH)
    if r is None:
        return None
    host = sum(r[c] for c in HOST) - sum(r[c] for c in FETCH)
    return float(host.mean()) * 1e3
