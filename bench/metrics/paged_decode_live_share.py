"""Kernels: share of the paged decode kernel's grid that live rows need.
The program's counters over the rounds that ended inside the window:
``decode_ctx_tokens`` (each live row's keys, position + 1, summed over
steps) over ``decode_grid_tokens`` (every slot times the step's
attention bucket, what the kernel's grid covers)."""

from bench import rounds


def read(run):
    lo, hi = run.window.open, run.window.close
    live = rounds.counted(run, "decode_ctx_tokens", lo, hi)
    grid = rounds.counted(run, "decode_grid_tokens", lo, hi)
    if live is None or not grid:
        return None
    return live / grid
