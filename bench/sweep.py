"""Knee sweep for an open-loop cell: the highest offered rate at which
the scheduler's queue does not grow across the window.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 0.5,1,1.5 [--out sweep.json]

One process: weights, engine and warm-up once, then the cell's traffic
at each rate in turn, in ascending order, until a rate fails.  A rate
holds when every request due in the window finished within the mix's
``drain_s`` and the mean queue depth over the last third of the window
is within 2 requests or 20% of that over the first third.  The result,
with the knee and 0.8 x the knee, goes to ``--out`` as JSON and to the
last line of standard output; a cell's traffic file records it with the
rate it fixes.  No reference check: the benchmark's own runs decide
``correct``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import run, spec, system  # noqa: E402
from bench.traffic import generate  # noqa: E402


def queue_thirds(ts, lo: float, hi: float):
    """Mean queue depth over each third of the window's rounds."""
    t = ts.get("t", np.zeros(0))
    q = ts.get("queue_depth", np.zeros(0))[(t >= lo) & (t < hi)]
    if len(q) < 3:
        return None
    return [float(np.mean(q[i * len(q) // 3:(i + 1) * len(q) // 3]))
            for i in range(3)]


def holds(failed: int, thirds) -> bool:
    if failed or thirds is None:
        return False
    first, last = thirds[0], thirds[2]
    return last <= first + 2 or last <= 1.2 * first


def one_rate(engine, traffic, rate, *, seed, seconds, vocab):
    traffic = dict(traffic, arrivals=dict(traffic["arrivals"],
                                          rate_per_s=rate))
    reqs = generate.open_loop(traffic, seed, seconds, vocab)
    win, records = asyncio.run(system.open_loop(
        engine, reqs, ramp_s=traffic["ramp_s"], seconds=seconds,
        drain_s=traffic["drain_s"], timeline=system.Timeline([])))
    counted = [records[r.uid] for r in reqs
               if traffic["ramp_s"] <= r.due_s < traffic["ramp_s"] + seconds]
    data = types.SimpleNamespace(loop="open", window=win, records=records,
                                 counted=counted)
    ts = system.timeseries(engine, win.session_t0)
    failed = sum(r.status != "ok" for r in counted)
    thirds = queue_thirds(ts, win.open, win.close)
    row = {"rate_per_s": rate, "counted": len(counted), "failed": failed,
           "queue_depth_by_third": thirds, "holds": holds(failed, thirds)}
    live = ts.get("live_slots")
    if live is not None and len(live):
        row["live_slots_mean"] = float(np.mean(live))
    for name in ("ttft_p95_s", "tbt_p95_ms", "output_tokens_per_s"):
        row[name] = spec.metric_reader(name)(data)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{cell.name}: only an open-loop mix has a knee")
    run.enable_compile_cache()
    devices = run.accelerator(cell.chips)
    import jax

    dims, family = cell.model, cell.family
    with jax.default_device(devices[0]):
        engine = system.build_engine(family, cell.config, dims,
                                     family.make_params(dims, args.seed))
        system.warmup(engine, cell.traffic, dims["vocab"])
        rows, knee = [], None
        for rate in sorted(float(x) for x in args.rates.split(",")):
            t0 = time.perf_counter()
            row = one_rate(engine, cell.traffic, rate, seed=args.seed,
                           seconds=args.seconds, vocab=dims["vocab"])
            row["wall_s"] = time.perf_counter() - t0
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
            if not row["holds"]:
                break
            knee = rate
    out = {"workload": cell.name, "seconds": args.seconds,
           "device_kind": devices[0].device_kind, "sweep": rows,
           "knee_per_s": knee,
           "fixed_rate_per_s": None if knee is None else round(0.8 * knee, 2)}
    if args.out:
        Path(args.out).write_text(json.dumps(out, indent=1) + "\n")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
