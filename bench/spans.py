"""A traced run of one cell that also reduces the program's spans.

  python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \
      [--out <dir>]

Runs ``bench/run.py`` with ``--trace 1`` and prints its result line.  From
the same trace it reduces the engine's ``serve.*`` spans
(``span_reduce.py``): count, seconds and self-seconds per span, the
device's idle seconds by innermost span, ``fetch_idle_share``, and the
trace's operations whose name holds ``flash`` (the named flash kernel,
as the chip names it).  These go to standard error as one JSON line
(``spans: {...}``) and, with ``--out``, to ``<dir>/<cell>-<seed>.json``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run, span_reduce  # noqa: E402
from bench.trace_reduce import OPS_LINE  # noqa: E402


def summary(events) -> dict:
    s = span_reduce.reduce_spans(events)
    flash: dict = {}
    for e in events:
        if e.line == OPS_LINE and "flash" in e.name:
            key = e.name[:100]
            flash[key] = flash.get(key, 0.0) + e.dur_ns * 1e-9
    if s is None:
        return {"flash_ops": flash}
    return {"window_s": s.window_s, "idle_s": s.idle_s,
            "fetch_idle_share": s.fetch_idle_share(),
            "spans": s.spans, "idle_by_span": s.idle_by_span,
            "flash_ops": flash}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--out")
    args, rest = ap.parse_known_args(argv)
    found = {}
    reduce = run.reduce

    def reduce_with_spans(events):
        found.update(summary(events))
        return reduce(events)

    run.reduce = reduce_with_spans
    run.main(["--workload", args.workload, "--seed", args.seed, *rest,
              "--trace", "1"])
    line = json.dumps(found)
    run.log(f"spans: {line}")
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{args.workload}-{args.seed}.json").write_text(line)


if __name__ == "__main__":
    main()
