"""Kernels: the flash prefill kernel's least possible time over its
device time in the trace, percent.  The operations and bytes are the
family's ``flash_kernel``'s, linear in the keys causal rows attend and in
the tokens computed, taken from the program's counters
(``prefill_causal_keys``, ``prefill_tokens``) over the rounds that ended
while the trace ran; the device time is the summed duration of the
named kernel's operations, every layer of every prefill.  Suffix
prefills, which take the paged kernel instead, count in those counters
too: the metric is read where the prefix cache is bypassed."""

from bench import rounds, work

KERNEL = "flash_attention_fwd_kernel"


def read(run):
    if run.trace is None or run.worklog.started is None:
        return None
    secs = sum(s for name, (_, s) in run.trace.ops.items()
               if name.lstrip("%").startswith(KERNEL))
    lo, hi = run.worklog.started, run.window.close
    keys = rounds.counted(run, "prefill_causal_keys", lo, hi)
    tokens = rounds.counted(run, "prefill_tokens", lo, hi)
    if secs <= 0 or not keys or not tokens:
        return None
    # flash_kernel(m, 1): one causal row over one key, and one token
    per_key, per_token = run.family.flash_kernel(run.model, 1)
    least = work.min_seconds(per_key * keys, per_token * tokens, run.peak)
    return 100.0 * least / secs
