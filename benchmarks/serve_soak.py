"""Continuous-arrival soak + overlap benchmark for the serving engine.

Two sections, both on the deterministic round clock (seeded arrivals,
reproducible schedules):

  soak     One long continuous-arrival session at a rate below
           saturation (so queueing delay stays bounded and any latency
           growth is the engine's fault, not the workload's).  Gates
           *drift*: the second half of the run must look like the
           first — TTFT percentiles may not degrade past a bounded
           factor, and the allocator's free-page floor may not sink
           (a sinking floor is a slow page leak / fragmentation
           building up).  Plus the standing invariants every serving
           benchmark gates: PARTITION (every request exactly one
           terminal status), LEAK (allocator audit clean, zero pages
           used after drain), PARITY (surviving outputs bit-identical
           to a fault-free closed-loop serve).
  overlap  An over-saturated workload (persistent queue, watermark
           shedding — the per-round host sweeps are O(queue) and are
           exactly the work the pipeline hides) served twice: serial
           (``pipeline=False``) and pipelined (``pipeline=True``),
           wall-clocked.  Outputs must match bit-for-bit; the
           rounds/s ratio is hard-gated: >= 1.15x in full mode on a
           multi-core host (the point of the dispatch/commit split),
           no-regression (>= 0.85x) in smoke or on a single core,
           where host/device overlap is physically impossible and the
           gate would measure scheduler noise, not the feature.
           Override with --overlap-gate.

  PYTHONPATH=src python benchmarks/serve_soak.py           # full
  PYTHONPATH=src python benchmarks/serve_soak.py --smoke   # CI
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import reduced_config
from repro.models.lm import Model
from repro.serve.async_engine import serve_open_loop
from repro.serve.engine import TERMINAL_STATUSES, ServeEngine
from repro.serve.workload import make_workload

_SECTIONS = ("soak", "overlap")


def _model():
    cfg = reduced_config("qwen2-1.5b")
    model = Model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, model, params


def _engine(model, params, **kw):
    kw = {"max_seq": 64, "batch_slots": 2, "temperature": 0.0, "seed": 0,
          "cache_layout": "paged", "page_size": 8, **kw}
    return ServeEngine(model, params, **kw)


def _workload(cfg, n: int, rate: float, seed: int):
    return make_workload(
        "poisson", n, vocab=cfg.vocab, seed=seed, rate=rate,
        prompt_median=8, prompt_sigma=0.5, prompt_min=3, prompt_max=24,
        out_median=6, out_sigma=0.4, out_min=2, out_max=12,
        priority_mix=[(0, 0.2), (1, 0.5), (2, 0.3)])


def _reference(model, params, wl, uids) -> Dict[int, List[int]]:
    """Fault-free closed-loop outputs for ``uids`` — the parity oracle
    (outputs are (uid, position)-keyed, so one batch serve covers any
    admitted subset)."""
    eng = _engine(model, params)
    return eng.serve([dataclasses.replace(t.request, generated=None)
                      for t in wl if t.request.uid in uids])


def _gate_invariants(tag: str, eng: ServeEngine, wl, ok, *,
                     ref: Dict[int, List[int]]):
    stats = eng.last_stats
    uids = [t.request.uid for t in wl]
    missing = [u for u in uids
               if stats.get(u, {}).get("status") not in TERMINAL_STATUSES]
    if missing:
        raise SystemExit(f"PARTITION BROKEN ({tag}): no terminal status "
                         f"for uids {missing}")
    pool = eng.last_pool_stats
    if pool is not None and (not pool.audit_ok or pool.used_pages != 0):
        raise SystemExit(f"ALLOCATOR LEAK ({tag}): audit_ok="
                         f"{pool.audit_ok} used_pages={pool.used_pages}")
    for u, toks in ok.items():
        if toks != ref[u]:
            raise SystemExit(f"PARITY BROKEN ({tag}, uid {u}): "
                             f"{toks} != {ref[u]}")


def _half_stats(stats, wl, timeseries, lo_frac: float,
                hi_frac: float) -> Dict:
    """TTFT p95 over one arrival-ordered window of the requests, plus
    the free-page floor over the matching window of rounds."""
    ordered = sorted(wl, key=lambda t: t.arrival_s)
    lo, hi = int(len(ordered) * lo_frac), int(len(ordered) * hi_frac)
    ttft = [stats[t.request.uid]["first_token_s"]
            - stats[t.request.uid]["enqueued_s"]
            for t in ordered[lo:hi]
            if "first_token_s" in stats.get(t.request.uid, {})]
    free = timeseries.get("free_pages") or []
    f_lo, f_hi = int(len(free) * lo_frac), int(len(free) * hi_frac)
    return {
        "ttft_p95_ms": (float(np.percentile(ttft, 95)) * 1e3
                        if ttft else None),
        "n_ttft": len(ttft),
        "free_floor": (min(free[f_lo:f_hi]) if f_hi > f_lo else None),
    }


def run_soak(model, params, cfg, smoke: bool = False,
             drift_factor: float = 2.0) -> List[Dict]:
    """One long under-saturation session; gate that the tail of the run
    behaves like the head."""
    n = 24 if smoke else 400
    rate = 0.2          # req/round: ~70% of the 2-slot service rate
    wl = _workload(cfg, n, rate, seed=29)
    eng = _engine(model, params, max_queue=max(n, 8),
                  queue_watermark=6, shed_priority=2)
    t0 = time.perf_counter()
    ok = asyncio.run(serve_open_loop(eng, wl, clock="round"))
    wall = time.perf_counter() - t0
    stats = eng.last_stats
    ref = _reference(model, params, wl, set(ok))
    _gate_invariants("soak", eng, wl, ok, ref=ref)

    ts = stats["timeseries"]
    first = _half_stats(stats, wl, ts, 0.0, 0.5)
    second = _half_stats(stats, wl, ts, 0.5, 1.0)
    rounds = ts["round"][-1] if ts["round"] else 1
    row = {
        "section": "soak", "n": n, "rate": rate, "rounds": rounds,
        "wall_s": wall, "rounds_per_s": rounds / max(wall, 1e-9),
        "statuses": stats["sla"]["statuses"],
        "first_half": first, "second_half": second,
        "overlap_s_mean": (stats["sla"].get("rounds") or {}).get(
            "overlap_s_mean"),
    }
    a, b = first["ttft_p95_ms"], second["ttft_p95_ms"]
    # absolute slack keeps sub-ms jitter from tripping the ratio
    if a is not None and b is not None \
            and b > drift_factor * a and b - a > 25.0:
        raise SystemExit(
            f"DRIFT GATE BROKEN (soak): second-half TTFT p95 {b:.2f}ms "
            f"vs first-half {a:.2f}ms exceeds {drift_factor:.1f}x — "
            f"latency degrades over time")
    fa, fb = first["free_floor"], second["free_floor"]
    if fa is not None and fb is not None and fb < fa - 1:
        raise SystemExit(
            f"FRAGMENTATION GATE BROKEN (soak): free-page floor sank "
            f"from {fa} (first half) to {fb} (second half) — pages are "
            f"leaking or fragmenting under sustained load")
    return [row]


def run_overlap(model, params, cfg, smoke: bool = False,
                gate=None) -> List[Dict]:
    """Serve an identical over-saturated workload serial and pipelined;
    gate parity and the wall-clock rounds/s ratio."""
    n = 16 if smoke else 160
    wl = _workload(cfg, n, 0.6, seed=31)
    engine_kw = dict(max_queue=64, queue_watermark=8, shed_priority=2)
    rows: List[Dict] = []
    results = {}
    for pipeline in (False, True):
        eng = _engine(model, params, pipeline=pipeline, **engine_kw)
        # warm the jit caches so compile time does not pollute the ratio
        eng.serve([dataclasses.replace(t.request, generated=None,
                                       uid=10_000 + t.request.uid)
                   for t in wl[:2]])
        t0 = time.perf_counter()
        ok = asyncio.run(serve_open_loop(eng, wl, clock="round"))
        wall = time.perf_counter() - t0
        stats = eng.last_stats
        ts = stats["timeseries"]
        rounds = ts["round"][-1] if ts["round"] else 1
        results[pipeline] = {"ok": ok, "wall": wall, "rounds": rounds}
        phases = stats["sla"].get("rounds") or {}
        rows.append({
            "section": "overlap", "pipeline": pipeline, "n": n,
            "rounds": rounds, "wall_s": wall,
            "rounds_per_s": rounds / max(wall, 1e-9),
            "dispatch_s_mean": phases.get("dispatch_s_mean"),
            "fetch_s_mean": phases.get("fetch_s_mean"),
            "commit_s_mean": phases.get("commit_s_mean"),
            "overlap_s_mean": phases.get("overlap_s_mean"),
            "statuses": stats["sla"]["statuses"],
        })
    if results[False]["ok"] != results[True]["ok"]:
        raise SystemExit("PARITY BROKEN (overlap): pipelined outputs "
                         "differ from serial")
    ratio = ((results[True]["rounds"] / max(results[True]["wall"], 1e-9))
             / max(results[False]["rounds"]
                   / max(results[False]["wall"], 1e-9), 1e-9))
    cores = os.cpu_count() or 1
    if gate is None:
        # overlap needs a second core to hide host work under the
        # device step; on one core (or in smoke, where runs are too
        # short to time) gate no-regression only
        gate = 1.15 if (cores >= 2 and not smoke) else 0.85
    rows.append({"section": "overlap", "pipeline": "ratio", "n": n,
                 "rounds_per_s_ratio": ratio, "gate": gate,
                 "cores": cores})
    if ratio < gate:
        raise SystemExit(
            f"OVERLAP GATE BROKEN: pipelined rounds/s is {ratio:.3f}x "
            f"serial (gate >= {gate:.2f}x on {cores} cores) — the "
            f"dispatch/commit split is not hiding host work")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (no perf claims)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the result rows as JSON")
    ap.add_argument("--section", default="all",
                    help="comma-separated subset of "
                         f"{', '.join(_SECTIONS)} (default: all)")
    ap.add_argument("--overlap-gate", type=float, default=None,
                    help="override the pipelined/serial rounds/s gate "
                         "(default: 1.15 full multi-core, 0.85 smoke "
                         "or single-core)")
    ap.add_argument("--drift-factor", type=float, default=2.0,
                    help="max second-half/first-half TTFT ratio the "
                         "soak tolerates")
    args = ap.parse_args(argv)
    sections = (set(_SECTIONS) if args.section == "all"
                else set(args.section.split(",")))
    unknown = sections - set(_SECTIONS)
    if unknown:
        ap.error(f"unknown section(s) {sorted(unknown)}; "
                 f"pick from {_SECTIONS}")
    cfg, model, params = _model()
    rows: List[Dict] = []

    if "soak" in sections:
        srows = run_soak(model, params, cfg, smoke=args.smoke,
                         drift_factor=args.drift_factor)
        r = srows[0]
        print("\n== Continuous-arrival soak: latency/fragmentation "
              "drift (round clock; parity/partition/leak gated) ==")
        print(f"  n={r['n']} rate={r['rate']}/round rounds={r['rounds']}"
              f" ({r['rounds_per_s']:.1f} rounds/s) "
              f"statuses={r['statuses']}")
        for half in ("first_half", "second_half"):
            h = r[half]
            ttft = h["ttft_p95_ms"]
            ttft = "n/a" if ttft is None else f"{ttft:.2f}ms"
            print(f"  {half:<12s} ttft_p95={ttft:>10s} "
                  f"free_floor={h['free_floor']} "
                  f"(n={h['n_ttft']})")
        print("gate PASSED: no TTFT drift, free-page floor held")
        rows += srows

    if "overlap" in sections:
        orows = run_overlap(model, params, cfg, smoke=args.smoke,
                            gate=args.overlap_gate)
        print("\n== Overlapped round pipeline: serial vs pipelined "
              "(identical workload, wall-clocked) ==")
        print(f"{'mode':>10s} {'rounds':>7s} {'wall_s':>8s} "
              f"{'rounds/s':>9s} {'overlap_us':>11s}")
        for r in orows:
            if r["pipeline"] == "ratio":
                continue
            mode = "pipelined" if r["pipeline"] else "serial"
            ov = (r["overlap_s_mean"] or 0.0) * 1e6
            print(f"{mode:>10s} {r['rounds']:7d} {r['wall_s']:8.2f} "
                  f"{r['rounds_per_s']:9.1f} {ov:11.1f}")
        ratio_row = orows[-1]
        print(f"  ratio {ratio_row['rounds_per_s_ratio']:.3f}x "
              f"(gate >= {ratio_row['gate']:.2f}x, "
              f"{ratio_row['cores']} cores)")
        print("gate PASSED: pipelined rounds/s within gate")
        rows += orows

    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"\nwrote {len(rows)} rows to {args.json}")


if __name__ == "__main__":
    main()
