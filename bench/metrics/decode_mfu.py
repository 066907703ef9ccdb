"""Model step: decode operations over decode device time and the chip's
peak, percent.  Operations come from the contexts each traced step
computed (the family's ``decode_step``); the mean per logged step is
scaled to the number of decode programs that the trace holds."""

from bench.metrics.decode_step_ms import DECODE


def read(run):
    if run.trace is None or not run.worklog.decode:
        return None
    n, s = run.trace.program_seconds(DECODE)
    if not n or s <= 0:
        return None
    flops = sum(run.family.decode_step(run.model, c)[0]
                for c in run.worklog.decode)
    flops *= n / len(run.worklog.decode)
    return 100.0 * flops / (s * run.peak["bf16_flops_per_s"])
