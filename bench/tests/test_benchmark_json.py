"""BENCHMARK.json holds the keys the harness reads, and every name it
holds resolves to a file of its own."""

import json
import re
from pathlib import Path

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_entries_resolve():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        conf = json.loads((ROOT / c["file"]).read_text())
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert conf["source"] == c["source"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = spec.load_cell(w["name"])
        assert cell.traffic["name"] == w["traffic"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(spec.metric_reader(m["name"]))
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        cells = m.get("workloads", [w["name"] for w in bench["workloads"]])
        for c in cells:
            mv = e2e[m["moves"]]
            assert "workloads" not in mv or c in mv["workloads"]
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
