"""Finds a cell's configuration, model family, traffic mix and metrics
by name.

``BENCHMARK.json`` names the cells; each configuration is a file under
``bench/configs/``, its model family ``bench/families/<family>.py``, each
traffic mix ``bench/traffic/<name>.json`` and each metric
``bench/metrics/<name>.py``.  Nothing here knows a cell, a family or a
metric by name, so a cell, configuration, family, mix or metric is added
by adding files and entries.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

from bench.families import REQUIRED

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's contents
    family: ModuleType              # bench/families/<config's family>.py
    traffic: Dict[str, Any]         # the traffic file's contents
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def model(self) -> Dict[str, Any]:
        return self.family.dims(self.config)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=conf,
        family=family(conf, root), traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def family(conf: Dict[str, Any], root: Path = ROOT) -> ModuleType:
    """``bench/families/<conf["family"]>.py``, loaded by path; stops
    with the known families where the configuration names none of them
    or the module lacks a name of the contract."""
    where = root / "bench" / "families"
    known = sorted(p.stem for p in where.glob("*.py")
                   if not p.stem.startswith("_"))
    name = conf.get("family")
    if name not in known:
        raise SystemExit(
            f"configuration {conf.get('name')!r} names family {name!r}; "
            f"the known families are {known} (bench/families/<family>.py)")
    module = _load(str(where / f"{name}.py"), f"bench.families.{name}")
    missing = [n for n in REQUIRED if not hasattr(module, n)]
    if missing:
        raise SystemExit(f"family {name!r} lacks {missing}; the known "
                         f"families are {known}")
    return module


@functools.lru_cache(maxsize=None)
def _load(path: str, name: str) -> ModuleType:
    """A module from its file, once per process (a family's jitted
    functions keep their compiled programs for the process)."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(run) -> float | None``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    return _load(str(path), f"bench.metrics.{name.replace('.', '_')}").read
