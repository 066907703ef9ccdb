"""Model step: how unevenly the router loads the held experts.  The
program's expert-layer counters over the rounds that ended inside the
window: the busiest held expert's rows (``moe_rows_max``, summed over
layers and steps) over the mean rows of a held expert,
``moe_routed_rows / experts_held`` summed alike.  1 is an even load.

It describes the traffic and the weights, not the program: the router's
weights are random from the seed, where a trained Qwen2-MoE router is
balanced by its auxiliary loss, and a program that serves the same
tokens exactly routes each of them the same way, so a change that keeps
the output exact moves it only through which tokens share a step.  What
it tells is how far the grouped matmul's work sits from a trained
router's.  In a decode step each held expert sees about 16
rows, far below the rows at which the kernel stops being bound by the
expert's weights, and nearly every held expert is hit whatever the skew:
a skew of 2.8 leaves the step's expert bytes, and so the kernel's share
of the step, about where a balanced router puts them.  A 1024-token
prefill gives a held expert about 128 rows, near the rows at which the
chip's compute takes over from its memory bandwidth: there the busiest
expert, at 2.8 times the mean, is bound by compute, and the skew
lengthens the kernel's time over a balanced router's."""

from bench import rounds


def read(run):
    held = run.model.get("experts_held")
    lo, hi = run.window.open, run.window.close
    rows = rounds.counted(run, "moe_routed_rows", lo, hi)
    busiest = rounds.counted(run, "moe_rows_max", lo, hi)
    if not held or not rows or busiest is None:
        return None
    return busiest * held / rows
