"""Model step: prefill operations of the tokens actually computed (full
prompts through ``jit(prefill_bucket_fn)``, uncached suffixes through
``jit(suffix_prefill_fn)``; padding not counted; the family's
``prefill``) over their device time and the chip's peak, percent."""

FULL, SUFFIX = "prefill_bucket_fn", "suffix_prefill_fn"


def read(run):
    if run.trace is None:
        return None
    prefill = run.family.prefill
    flops = secs = 0.0
    for part, logged, cost in (
            (FULL, run.worklog.prefill, lambda n: prefill(run.model, n)),
            (SUFFIX, run.worklog.suffix,
             lambda sn: prefill(run.model, sn[1], sn[0]))):
        n, s = run.trace.program_seconds(part)
        if n and logged:
            flops += sum(cost(x)[0] for x in logged) * n / len(logged)
            secs += s
    if secs <= 0:
        return None
    return 100.0 * flops / (secs * run.peak["bf16_flops_per_s"])
