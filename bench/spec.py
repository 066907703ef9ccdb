"""Finds a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` names the cells; each configuration is a file under
``bench/configs/``, each traffic mix ``bench/traffic/<name>.json`` and
each metric ``bench/metrics/<name>.py``.  Nothing here knows a cell by
name, so a cell, configuration, mix or metric is added by adding files
and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's contents
    traffic: Dict[str, Any]         # the traffic file's contents
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def model(self) -> Dict[str, Any]:
        return model_dims(self.config)


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
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=conf, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def model_dims(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The published config's keys, as the weights and the reference
    read them."""
    c = conf["config"]
    heads = c["num_attention_heads"]
    return {
        "n_layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "n_heads": heads,
        "n_kv_heads": c["num_key_value_heads"],
        "d_head": c.get("head_dim", c["hidden_size"] // heads),
        "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "rope_theta": float(c["rope_theta"]),
    }


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(run) -> float | None``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read
