"""Kernels: the paged flash-decode kernel's least possible time (the
larger of its operations over peak FLOP/s and its K/V, q and o bytes
over peak bandwidth, by the family's ``decode_kernel``; bandwidth bounds
it) over its device time in the trace, percent.  A step makes the
family's ``attention_layers`` calls of the kernel."""

from bench import work

KERNEL = ("paged_decode_attention_op",)


def read(run):
    if run.trace is None or not run.worklog.decode:
        return None
    calls, s = run.trace.op_seconds(KERNEL)
    if not calls or s <= 0:
        return None
    steps = calls / run.family.attention_layers(run.model)
    least = sum(work.min_seconds(*run.family.decode_kernel(run.model, c),
                                 run.peak)
                for c in run.worklog.decode)
    least *= steps / len(run.worklog.decode)
    return 100.0 * least / s
