"""SLA accounting for the serving engine.

Two latency figures define a serving SLA and neither is a mean:

  TTFT  time-to-first-token, enqueue -> first sampled token.  Queueing
        + admission + prefill; the number a user staring at a blank
        screen experiences.
  TBT   time-between-tokens, the gap between consecutive token
        emissions of one request.  Decode cadence; the number a user
        watching tokens stream experiences.  A speculative window that
        commits k tokens at once contributes one real gap and k-1
        zeros — the burst is how the tokens actually arrived.

Both are summarized as p50/p95/p99 percentiles (tail latency is the
SLA), alongside goodput — tokens per second delivered by requests that
finished ``ok``; shed/timeout/failed work is by definition not good —
and the terminal-status census.  The engine attaches the summary to
``last_stats["sla"]`` at the end of every session (and on abort), so
closed-loop ``serve()`` calls, the async open-loop server, benchmarks,
and the launch CLI all read one schema.

Host-side and engine-agnostic: the input is the engine's ``last_stats``
ledger (int keys = per-request entries), not the engine itself.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

PERCENTILES = (50, 95, 99)


def percentiles(samples: List[float]) -> Dict[str, Optional[float]]:
    """p50/p95/p99 + mean/max over ``samples`` (None-filled when empty,
    so consumers can format a row without special-casing)."""
    out: Dict[str, Optional[float]] = {f"p{p}": None for p in PERCENTILES}
    out.update(mean=None, max=None, n=len(samples))
    if samples:
        a = np.asarray(samples, np.float64)
        for p in PERCENTILES:
            out[f"p{p}"] = float(np.percentile(a, p))
        out["mean"] = float(a.mean())
        out["max"] = float(a.max())
    return out


def summarize(stats: Dict[Any, Any], *, tbt_s: List[float],
              wall_s: float,
              timeseries: Optional[Dict[str, list]] = None
              ) -> Dict[str, Any]:
    """One SLA summary from an engine status ledger.

    ``stats``: the engine's per-session ledger — int keys are requests
    (dicts with ``enqueued_s`` / ``first_token_s`` / ``status`` /
    ``tokens``), string keys (stragglers, timeseries) are ignored.
    ``tbt_s``: raw time-between-token gap samples, seconds.
    ``wall_s``: session wall time, the goodput denominator.
    ``timeseries``: optional per-round engine timeseries; when it
    carries the phase columns (``dispatch_s`` / ``fetch_s`` /
    ``commit_s`` / ``overlap_s``) the summary gains a ``rounds`` block
    with their means — how much host time went to issuing the decode
    step, was spent blocked in its fetch, went to the accounting after
    it, and was hidden under an in-flight device step.
    """
    per = {u: s for u, s in stats.items() if isinstance(u, int)}
    ttft = [s["first_token_s"] - s.get("enqueued_s", 0.0)
            for s in per.values() if "first_token_s" in s]
    statuses: Dict[str, int] = {}
    ok_tokens = 0
    for s in per.values():
        key = s.get("status") or "in-flight"
        statuses[key] = statuses.get(key, 0) + 1
        if s.get("status") == "ok":
            ok_tokens += int(s.get("tokens", 0))
    out = {
        "requests": len(per),
        "statuses": statuses,
        "ttft_ms": percentiles([t * 1e3 for t in ttft]),
        "tbt_ms": percentiles([t * 1e3 for t in tbt_s]),
        "ok_tokens": ok_tokens,
        "goodput_tok_s": ok_tokens / max(wall_s, 1e-9),
        "wall_s": wall_s,
    }
    if timeseries and timeseries.get("round"):
        rounds: Dict[str, Any] = {"n": len(timeseries["round"])}
        for col in ("dispatch_s", "fetch_s", "commit_s", "overlap_s"):
            vals = timeseries.get(col) or []
            rounds[f"{col}_mean"] = (float(np.mean(vals)) if vals
                                     else None)
        out["rounds"] = rounds
    return out


def merge_ledgers(ledgers: List[Dict[Any, Any]]) -> Dict[Any, Any]:
    """Merge per-worker status ledgers into one fleet ledger (int keys
    only — per-worker string keys like stragglers/timeseries do not
    aggregate meaningfully).  Uids are fleet-unique; when one appears in
    several ledgers (a request that failed with its replica and was
    re-served elsewhere) the *later* ledger wins, so pass ledgers in
    worker-sweep order with re-routes after their dead source."""
    merged: Dict[Any, Any] = {}
    for ledger in ledgers:
        for uid, entry in ledger.items():
            if isinstance(uid, int):
                merged[uid] = entry
    return merged


def fleet_summary(per_worker: Dict[Any, Dict[Any, Any]], *,
                  tbt_s: List[float], wall_s: float) -> Dict[str, Any]:
    """Fleet-level SLA: one :func:`summarize` over the merged ledgers of
    every worker, plus the per-replica census a capacity planner needs
    (requests and terminal statuses per worker).  ``per_worker`` maps
    worker id -> that worker's session ledger."""
    order = sorted(per_worker, key=str)
    fleet = summarize(merge_ledgers([per_worker[w] for w in order]),
                      tbt_s=tbt_s, wall_s=wall_s)
    replicas = {}
    for wid in order:
        per = {u: s for u, s in per_worker[wid].items()
               if isinstance(u, int)}
        statuses: Dict[str, int] = {}
        for s in per.values():
            key = s.get("status") or "in-flight"
            statuses[key] = statuses.get(key, 0) + 1
        replicas[str(wid)] = {"requests": len(per), "statuses": statuses}
    fleet["replicas"] = replicas
    return fleet


def format_summary(sla: Dict[str, Any]) -> str:
    """Human-readable SLA block (launch CLI + benchmark stdout)."""
    def row(name, pct):
        cells = " ".join(
            f"{k}={pct[k]:8.2f}ms" if pct[k] is not None else f"{k}=     n/a"
            for k in ("p50", "p95", "p99"))
        return f"  {name:<6} {cells}  (n={pct['n']})"

    statuses = " ".join(f"{k}={v}" for k, v in
                        sorted(sla["statuses"].items()))
    return "\n".join([
        row("ttft", sla["ttft_ms"]),
        row("tbt", sla["tbt_ms"]),
        f"  goodput {sla['goodput_tok_s']:.1f} tok/s "
        f"({sla['ok_tokens']} ok tokens / {sla['wall_s']:.2f}s)",
        f"  statuses: {statuses}",
    ])
