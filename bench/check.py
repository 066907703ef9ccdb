"""Decides ``correct``: what the timed window served, against the plain
float32 reference of the configuration's model family (its ``gaps``).

Once the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed, is run through the
reference: the longest request, then others in a seeded order until the
sample holds ``MIN_TOKENS`` served tokens and ``MIN_REQUESTS`` requests
(or the configuration's ``check_requests``: a random-weight model may
serve one token over and over, where no precision tells two forwards
apart, so a configuration whose requests often do so samples more).
Each sequence is the prompt followed by the served tokens, so the
reference sees exactly the contexts the served path computed.  The
numbers compared, each with its limit:

* ``logit_gap``: the widest gap, over every served token of the sample,
  by which the served token's reference logit lies below the reference's
  best logit at that position (greedy serving picks the best; bf16
  rounding may pick a near tie).  Limit: the configuration's
  ``logit_gap_limit``.
* ``length_mismatch``: sampled requests that served another number of
  tokens than they asked for.  Limit 0.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

MIN_TOKENS = 512
MIN_REQUESTS = 3
NO_READING = 1e30


def expected_tokens(rec, max_seq: int) -> int:
    return min(rec.max_new, max_seq - rec.n_prompt)


def sample(finished: List, seed: int,
           min_requests: int = MIN_REQUESTS) -> List:
    """The longest finished request, then others in a seeded order."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-(r.n_prompt + len(r.tokens)),
                                            r.uid))
    rest = order[1:]
    rng = np.random.default_rng([seed, 7])
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, served = [order[0]], len(order[0].tokens)
    for r in rest:
        if served >= MIN_TOKENS and len(out) >= min_requests:
            break
        out.append(r)
        served += len(r.tokens)
    return out


def compare(family, dims: Dict, seed: int, picked: List, *, limit: float,
            max_seq: int, control: bool = False) -> Dict[str, Dict]:
    """The numbers compared, each ``{"value", "limit"}``; ``family`` is
    the configuration's module under ``bench/families/``."""
    seqs = [(np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)]),
             r.n_prompt) for r in picked if r.tokens]
    sound, ctl = (family.gaps(dims, seed, seqs, control=control)
                  if seqs else ([], []))
    widest = _widest(ctl if control else sound)
    mismatch = sum(len(r.tokens) != expected_tokens(r, max_seq)
                   for r in picked)
    out = {"logit_gap": {"value": widest, "limit": limit},
           "length_mismatch": {"value": mismatch, "limit": 0},
           "served_tokens": {"value": sum(len(r.tokens) for r in picked),
                             "limit": MIN_TOKENS}}
    if control:
        # the program's own reading on the same sample, for the record
        out["program_logit_gap"] = {"value": _widest(sound), "limit": limit}
    return out


def _widest(gaps) -> float:
    """The widest gap; ``NO_READING`` where there is none or it is not
    finite (JSON has no infinity)."""
    widest = max((float(np.max(g)) for g in gaps if g.size), default=None)
    return widest if widest is not None and np.isfinite(widest) \
        else NO_READING


def passed(checked: Dict[str, Dict]) -> bool:
    return (checked["logit_gap"]["value"] <= checked["logit_gap"]["limit"]
            and checked["length_mismatch"]["value"] == 0
            and checked["served_tokens"]["value"]
            >= checked["served_tokens"]["limit"])
