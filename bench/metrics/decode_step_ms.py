"""Model step: device milliseconds per decode program (the engine's
``jit(paged_step_fn)``), from the trace."""

DECODE = "paged_step_fn"


def read(run):
    if run.trace is None:
        return None
    n, s = run.trace.program_seconds(DECODE)
    return s / n * 1e3 if n else None
