"""Scheduler: host milliseconds of admission per request a prefill
admitted.  Read from the program's own spans: the ``serve.admit`` phase
of each round that dispatched a ``serve.prefill`` (its radix walk,
prefill dispatch, page scatter and first-token fetch), summed over the
rounds that ended inside the window before the trace began, over the
program's ``admissions`` counter in those rounds."""

from bench import rounds


def read(run):
    lo, hi = run.window.open, rounds.untraced_end(run)
    r = rounds.rows(run, lo, hi, "admit_s", "prefill_s")
    admitted = rounds.per_round_counts(run, "admissions", lo, hi)
    if r is None or admitted is None:
        return None
    with_prefill = r["prefill_s"] > 0
    n = admitted[with_prefill].sum()
    return float(r["admit_s"][with_prefill].sum() / n) * 1e3 if n else None
