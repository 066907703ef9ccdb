"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and makes its requests from ``--seed``.

Lengths are clipped lognormals and arrivals a Poisson process (or the
two-state Markov-modulated Poisson process, ``mmpp``), as in the
program's ``serve/workload.py``, of which this is a copy kept with the
benchmark.  Unlike that module, lengths, Poisson gaps and tenant counts
are drawn stratified: each segment of the run (the ramp before the
window, the window, the tail after it) holds the same set of values for
every seed, at the quantiles ``(i + 0.5) / n`` of its distribution, and
the seed chooses their order and the token ids.  So runs with different
seeds do the same work, and their spread is the system's.  A mix that
gives ``"schedule_seed"`` draws that order from it instead: every run
replays one schedule of sizes and due times, and ``--seed`` chooses the
token ids alone (an open-loop tail that rests on which long prompts
arrive back to back would otherwise read the order more than the
system).

Open loop (``"loop": "open"``): requests are due at fixed times from the
start of arrivals; the window opens ``ramp_s`` later.  Closed loop
(``"loop": "closed"``): ``clients`` clients each send their next request
when the last one ends; a client's first request carries a random
residual share of its output length, so completions are spread from the
start.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    uid: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due_s: float = 0.0          # open loop: seconds after arrivals start
    client: int = -1            # closed loop: the client that sends it
    tenant: int = -1


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, rng: np.random.Generator, *, median: float,
                      sigma: float, min: int, max: int) -> np.ndarray:
    """``n`` clipped lognormal lengths at the stratified quantiles, in an
    order drawn from ``rng``."""
    z = np.asarray([NormalDist().inv_cdf(q) for q in quantiles(n)])
    raw = np.exp(math.log(median) + sigma * z)
    return rng.permutation(np.clip(np.round(raw).astype(int), min, max))


def poisson_arrivals(n: int, rate: float, span: float,
                     rng: np.random.Generator) -> np.ndarray:
    """``n`` arrivals over ``span`` seconds: exponential gaps at the
    stratified quantiles, shuffled, scaled to fill the span exactly."""
    if rate <= 0:
        raise ValueError(f"rate must be > 0; got {rate}")
    gaps = rng.permutation(-np.log1p(-quantiles(n)) / rate)
    gaps *= span / gaps.sum()
    return np.cumsum(gaps) - gaps[0]


def mmpp_arrivals(n: int, rate: float, span: float,
                  rng: np.random.Generator, *, burst_factor: float,
                  mean_dwell: float) -> np.ndarray:
    """MMPP-2: calm state at ``rate / burst_factor``, burst state at
    ``rate * burst_factor``, switching after a geometric dwell of
    ``mean_dwell`` arrivals; scaled to fill the span."""
    if burst_factor < 1.0:
        raise ValueError(f"burst_factor must be >= 1; got {burst_factor}")
    rates = (rate / burst_factor, rate * burst_factor)
    state, t, out = 0, 0.0, []
    for _ in range(n):
        out.append(t)
        t += rng.exponential(1.0 / rates[state])
        if rng.random() < 1.0 / max(mean_dwell, 1.0):
            state = 1 - state
    out = np.asarray(out)
    return out * (span / max(t, 1e-9))


def zipf_counts(n: int, count: int, s: float) -> np.ndarray:
    """Requests per tenant: Zipf weights 1 / rank**s, rounded to whole
    requests by largest remainder, so they sum to ``n``."""
    w = 1.0 / np.power(np.arange(1, count + 1, dtype=np.float64), s)
    exact = n * w / w.sum()
    out = np.floor(exact).astype(int)
    rest = np.argsort(-(exact - out))[:n - out.sum()]
    out[rest] += 1
    return out


def _arrivals(spec: Dict, n: int, span: float, rng) -> np.ndarray:
    a = spec["arrivals"]
    if a["process"] == "poisson":
        return poisson_arrivals(n, a["rate_per_s"], span, rng)
    if a["process"] == "mmpp":
        return mmpp_arrivals(n, a["rate_per_s"], span, rng,
                             burst_factor=a["burst_factor"],
                             mean_dwell=a["mean_dwell"])
    raise ValueError(f"unknown arrival process {a['process']!r}")


def _prompts(spec: Dict, n: int, vocab: int, rng,
             systems: Optional[List[np.ndarray]], order=None):
    """Prompt token arrays (and tenants) for ``n`` requests; lengths and
    tenants in an order drawn from ``order`` (default ``rng``), token ids
    from ``rng``."""
    order = rng if order is None else order
    lens = lognormal_lengths(n, order, **spec["prompt"])
    tenants = np.full(n, -1)
    if systems is not None:
        t = spec["tenants"]
        tenants = order.permutation(np.repeat(
            np.arange(t["count"]), zipf_counts(n, t["count"], t["zipf_s"])))
    out = []
    for i in range(n):
        own = rng.integers(0, vocab, int(lens[i]), dtype=np.int32)
        out.append(own if tenants[i] < 0
                   else np.concatenate([systems[tenants[i]], own]))
    return out, tenants


def open_loop(spec: Dict, seed: int, seconds: float,
              vocab: int) -> List[Req]:
    """Requests due over ramp, window and tail, in arrival order."""
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(spec["schedule_seed"])
             if "schedule_seed" in spec else rng)
    systems = None
    if spec.get("tenants"):
        t = spec["tenants"]
        systems = [rng.integers(0, vocab, t["system_len"], dtype=np.int32)
                   for _ in range(t["count"])]
    rate = spec["arrivals"]["rate_per_s"]
    segments = [(0.0, spec["ramp_s"]), (spec["ramp_s"], seconds),
                (spec["ramp_s"] + seconds, spec["drain_s"])]
    out: List[Req] = []
    for start, span in segments:
        n = max(1, int(round(rate * span)))
        due = start + _arrivals(spec, n, span, order)
        prompts, tenants = _prompts(spec, n, vocab, rng, systems, order)
        outs = lognormal_lengths(n, order, **spec["output"])
        for i in range(n):
            out.append(Req(uid=len(out), prompt=prompts[i],
                           max_new=int(outs[i]), due_s=float(due[i]),
                           tenant=int(tenants[i])))
    return out


def closed_loop(spec: Dict, seed: int, vocab: int) -> List[List[Req]]:
    """Per client, the requests it sends in turn."""
    rng = np.random.default_rng(seed)
    c, k = spec["clients"], spec["per_client"]
    n = c * k
    prompts, _ = _prompts(spec, n, vocab, rng, None)
    outs = lognormal_lengths(n, rng, **spec["output"])
    residual = rng.permutation(quantiles(c))
    clients: List[List[Req]] = []
    for ci in range(c):
        reqs = []
        for j in range(k):
            i = ci * k + j
            m = int(outs[i])
            if j == 0:
                m = max(1, int(round(residual[ci] * m)))
            reqs.append(Req(uid=i, prompt=prompts[i], max_new=m, client=ci))
        clients.append(reqs)
    return clients


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (the ``numpy`` default), as the
    program's ``serve/sla.py`` computes it; nan for no values."""
    if len(values) == 0:
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))
