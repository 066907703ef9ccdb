"""Kernels: the grouped matmul's least possible time over its device
time in the trace, percent.  The operations and bytes are the family's
``expert_kernel`` on the program's expert-layer counters
(``moe_routed_rows``, ``moe_experts_hit``, summed over layers, prefills
and decode steps) over the rounds that ended while the trace ran; the
device time is the summed duration of the named kernel's operations
(``grouped_matmul_op``: gate, up and down projections of every layer).
A program or a family without them reads nothing."""

from bench import rounds, work

KERNEL = ("grouped_matmul_op",)


def read(run):
    if (run.trace is None or run.worklog.started is None
            or not hasattr(run.family, "expert_kernel")):
        return None
    _, secs = run.trace.op_seconds(KERNEL)
    lo, hi = run.worklog.started, run.window.close
    rows = rounds.counted(run, "moe_routed_rows", lo, hi)
    hit = rounds.counted(run, "moe_experts_hit", lo, hi)
    if secs <= 0 or not rows or not hit:
        return None
    least = work.min_seconds(*run.family.expert_kernel(run.model, rows, hit),
                             run.peak)
    return 100.0 * least / secs
