"""KV cache / prefix index: prompt tokens served from cached pages
(the ledger's ``cached_prefix_tokens``) over all prompt tokens, for the
requests due in the window that were admitted."""


def read(run):
    admitted = [r for r in run.counted if r.admitted is not None]
    total = sum(r.n_prompt for r in admitted)
    if not total:
        return None
    return sum(r.cached_prefix for r in admitted) / total
