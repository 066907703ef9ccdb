"""Benchmark entry: one cell, one seed, one run.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` (its configuration under
``bench/configs/``, the configuration's model family under
``bench/families/``, its traffic mix under ``bench/traffic/``), makes
the family's weights on the device from the seed, builds the serving
engine, warms up every shape the mix can reach, then drives the engine
open loop or closed loop for ``--seconds`` and reports the cell's
end-to-end metrics (``--trace 0``) or its per-layer metrics read from a
profiler trace of the window's last seconds (``--trace 1``).  After the
window the program's state is freed and a sample of what it served is
compared with the family's float32 reference (``check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``checked``, the numbers compared with their
limits, which are also the last lines of standard error.  With no TPU,
or fewer chips than the cell asks for, it exits non-zero and prints no
result.

``--control 1`` runs the same window but computes the comparison with
the float8 control in the program's place (see the family's ``gaps``
under ``bench/families/``); it has to come out not correct.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, spec, system  # noqa: E402
from bench.trace_reduce import (  # noqa: E402
    WINDOW, latest_xplane, read_events, reduce)
from bench.traffic import generate  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_SECONDS = 10.0


class NoAccelerator(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"bench/run.py: {msg}")


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else ``.jax_cache/`` at the checkout's root, a fixed path.  Every
    program is kept, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def accelerator(chips: int):
    """The chips to run on; refuses a host without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devices[0].platform!r} "
                            "devices; the benchmark never runs elsewhere")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX sees "
                            f"{len(devices)}")
    return devices[:chips]


def peak_of(kind: str) -> Dict[str, Any]:
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in peaks:
        raise NoAccelerator(f"device kind {kind!r} is not in "
                            f"bench/peaks.json ({sorted(peaks)})")
    return peaks[kind]


class CompileCounter:
    """Programs JAX had to trace or fetch (compile, or load from the
    persistent cache) while ``active``."""

    EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.active = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **kw: self._event(event))

    def _event(self, event, **_):
        if self.active and event in self.counts:
            self.counts[event] += 1


@dataclasses.dataclass
class RunData:
    """What the metric readers read."""
    loop: str
    setup_s: float
    window: Any
    records: Dict[int, Any]
    counted: List[Any]
    timeseries: Dict[str, Any]
    num_pages: int
    model: Dict[str, Any]
    family: Any                 # the cell's module under bench/families/
    peak: Dict[str, Any]
    worklog: Any                # system.WorkLog
    trace: Any = None


def _traced(timeline, log_, tmp: str, seconds: float):
    """Timeline entries that trace the last ``TRACE_SECONDS`` of the
    window, between engine rounds.  Stopping the profiler holds the host
    for seconds while it collects the trace; at the window's close that
    stall delays only what is still running, and ledger readers count
    the requests due before the trace began (``WorkLog.started``)."""
    import jax

    span = min(TRACE_SECONDS, seconds / 2)
    at = seconds - span
    ann = []    # made once the profiler runs, or it records nothing

    def start():
        log_.started = time.perf_counter()
        jax.profiler.start_trace(tmp)
        ann.append(jax.profiler.TraceAnnotation(WINDOW))
        ann[0].__enter__()
        log_.active = True

    def stop():
        log_.active = False
        ann[0].__exit__(None, None, None)
        jax.profiler.stop_trace()

    timeline.events += [(at, start), (at + span, stop)]
    timeline.events.sort(key=lambda e: e[0])


def _serve(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
           devices, fault: Optional[Callable], t_start: float):
    """Weights, engine, warm-up and the window.  Returns plain host data
    only, so that nothing of the program outlives it."""
    import jax

    dims, conf, traffic = cell.model, cell.config, cell.traffic
    counter = CompileCounter()
    params = cell.family.make_params(dims, seed)
    engine = system.build_engine(cell.family, conf, dims, params)
    n_warm = system.warmup(engine, traffic, dims["vocab"])
    log(f"warm-up: {n_warm} requests over "
        f"{len(system.warmup_lengths(engine, traffic))} decode buckets")
    work = system.WorkLog()
    if fault is not None:
        fault(engine)
    holder = {}
    if trace:
        system.instrument(engine, lambda: holder["srv"]._st, work)
    timeline = system.Timeline([
        (0.0, lambda: setattr(counter, "active", True)),
        (seconds, lambda: setattr(counter, "active", False))])
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    if trace:
        _traced(timeline, work, tmp, seconds)
    on_start = lambda srv: holder.__setitem__("srv", srv)  # noqa: E731
    if traffic["loop"] == "open":
        reqs = generate.open_loop(traffic, seed, seconds, dims["vocab"])
        win, records = asyncio.run(system.open_loop(
            engine, reqs, ramp_s=traffic["ramp_s"], seconds=seconds,
            drain_s=traffic["drain_s"], timeline=timeline,
            on_start=on_start))
        counted = [records[r.uid] for r in reqs
                   if traffic["ramp_s"] <= r.due_s
                   < traffic["ramp_s"] + seconds]
    else:
        clients = generate.closed_loop(traffic, seed, dims["vocab"])
        win, records = asyncio.run(system.closed_loop(
            engine, clients, ramp_s=traffic["ramp_s"], seconds=seconds,
            timeline=timeline, on_start=on_start))
        counted = [r for r in records.values()
                   if r.status is not None and not r.cancelled_by_bench
                   and r.times and win.open <= r.times[-1] < win.close]
    counter.active = False
    ts = system.timeseries(engine, win.session_t0)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    reduced = None
    if trace:
        events = read_events(latest_xplane(tmp))
        _log_trace_names(events)
        reduced = reduce(events)
    shutil.rmtree(tmp, ignore_errors=True)
    data = RunData(loop=traffic["loop"], setup_s=win.open - t_start,
                   window=win, records=records, counted=counted,
                   timeseries=ts, num_pages=engine.num_pages, model=dims,
                   family=cell.family, peak={}, worklog=work,
                   trace=reduced)
    late = sorted(win.lateness) or [0.0]
    log(f"window: {seconds} s, {len(records)} requests submitted, "
        f"{len(counted)} counted; generator late by p50 "
        f"{late[len(late) // 2]:.6f} s, max {late[-1]:.6f} s")
    log(f"programs fetched inside the window (compiled or loaded from "
        f"the cache): {counter.counts[CompileCounter.EVENTS[0]]}; "
        f"traced: {counter.counts[CompileCounter.EVENTS[1]]}")
    return data, int(memory_peak), engine.max_seq


def _log_trace_names(events):
    """The trace's planes and lines, and the busiest names on each
    device line: what the reduction keys on, as the chip names it."""
    lines: Dict[tuple, Dict[str, float]] = {}
    for e in events:
        by_name = lines.setdefault((e.plane, e.line), {})
        by_name[e.name] = by_name.get(e.name, 0.0) + e.dur_ns
    for (plane, line), by_name in sorted(lines.items()):
        if not plane.startswith("/device:"):
            by_name = {n: d for n, d in by_name.items()
                       if n.startswith(("bench.", "engine."))}
            if not by_name:
                continue
        top = sorted(by_name.items(), key=lambda x: -x[1])[:6]
        log(f"trace line {plane} | {line}: {len(by_name)} names; "
            + ", ".join(f"{n[:60]}={d * 1e-9:.4f}s" for n, d in top))


def _free(devices):
    """Drop every device array the program left, before the reference
    runs; logs what the chip then holds."""
    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()
    gc.collect()
    stats = devices[0].memory_stats() or {}
    log(f"before the reference: bytes_in_use={stats.get('bytes_in_use')} "
        f"largest_free_block={stats.get('largest_free_block_bytes')}")


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             devices, peak: Dict[str, Any], control: bool = False,
             fault: Optional[Callable] = None,
             t_start: float = T_START) -> Dict[str, Any]:
    """One run; returns the result object (``checked`` last)."""
    import jax

    with jax.default_device(devices[0]):
        data, memory_peak, max_seq = _serve(
            cell, seed=seed, seconds=seconds, trace=trace, devices=devices,
            fault=fault, t_start=t_start)
    data.peak = peak
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.metric_reader(m["name"])(data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    counted = data.counted
    finished = [r for r in counted if r.status == "ok"]
    statuses = {}
    for r in counted:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    log(f"counted requests by status: {statuses}")

    _free(devices)
    picked = check.sample(finished, seed, cell.config.get(
        "check_requests", check.MIN_REQUESTS))
    with jax.default_device(devices[0]):
        checked = check.compare(
            cell.family, cell.model, seed, picked,
            limit=cell.config["logit_gap_limit"], max_seq=max_seq,
            control=control)
    log(f"checked {len(picked)} requests (uids "
        f"{[r.uid for r in picked]}) against the float32 reference"
        + (" with the float8 control in the program's place"
           if control else ""))

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {"correct": check.passed(checked),
                              "attempted": len(counted),
                              "failed": len(counted) - len(finished),
                              "metrics": metrics, "device": device}
    if data.trace is not None:
        device["busy_s"] = data.trace.busy_s
        device["window_s"] = data.trace.window_s
        result["breakdown"] = {"device_ops": data.trace.top_ops(),
                               "idle_gaps": data.trace.top_idle()}
    result["checked"] = checked
    for name, c in checked.items():
        log(f"checked {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    import repro.serve.async_engine  # noqa: F401  the system under test
    cache = enable_compile_cache()
    devices = accelerator(cell.chips)
    peak = peak_of(devices[0].device_kind)
    log(f"cell {cell.name} on {len(devices)} x {devices[0].device_kind}; "
        f"compile cache {cache}")
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices, peak=peak,
                      control=bool(args.control))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
