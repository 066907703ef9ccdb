"""The roofline, and what every family's operation and byte counts share.

Each family (``bench/families/<family>.py``) counts the operations and
bytes of its own decode step, prefill and attention kernels from the
lengths they actually computed (never from padded shapes).  A
multiply-add counts as two operations.  Bytes are the least a step must
move through HBM: every weight once, the K/V it reads and writes, and
for a kernel its inputs and outputs once.
"""

from __future__ import annotations

BYTES = 2       # bf16 weights and K/V, as the configurations serve


def causal_keys(n: int, start: int = 0) -> float:
    """Keys seen by ``n`` causal rows after ``start`` cached tokens."""
    return n * start + n * (n + 1) / 2.0


def min_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
