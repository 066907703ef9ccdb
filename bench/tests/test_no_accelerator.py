"""The harness refuses a host without the chip, and a device that the
peaks table does not know: non-zero exit, no result line."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]


def test_cpu_host_exits_non_zero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2-1.5b.chat-poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        run.peak_of("cpu")
    assert run.peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_directory_with_only_the_benchmark_exits_non_zero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2-1.5b.chat-poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert proc.stdout.strip() == ""
