"""Kernels: the paged flash-decode kernel's least possible time (the
larger of its operations over peak FLOP/s and its K/V, q and o bytes
over peak bandwidth; bandwidth bounds it) over its device time in the
trace, percent."""

from bench import work

KERNEL = ("paged_decode_attention_op",)


def read(run):
    if run.trace is None or not run.work.decode:
        return None
    calls, s = run.trace.op_seconds(KERNEL)
    if not calls or s <= 0:
        return None
    steps = calls / run.model["n_layers"]
    least = sum(work.min_seconds(*work.decode_kernel(run.model, c), run.peak)
                for c in run.work.decode)
    least *= steps / len(run.work.decode)
    return 100.0 * least / s
