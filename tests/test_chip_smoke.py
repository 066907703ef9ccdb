"""chip_smoke.py: it refuses to run without a TPU, and its phases run
end to end at reduced size on the CPU (interpret-mode kernels).  Also the
entry points' persistent compilation cache location."""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.configs.registry import reduced_config
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_to_run_without_the_chip(tmp_path, alone):
    """On the CPU, or copied away from the rest of the repo, the script
    exits non-zero and prints no result line."""
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    r = subprocess.run([sys.executable, script],
                       cwd=os.path.dirname(script),
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if not alone:
        assert "no TPU" in r.stderr


def test_phases_at_reduced_size(monkeypatch):
    cs = _load()
    monkeypatch.setattr(cs, "PROMPT_LENS", (8, 12, 16, 24))
    monkeypatch.setattr(cs, "MAX_NEW", 4)
    monkeypatch.setattr(cs, "KV_BYTES", 1 << 20)
    cfg = reduced_config(cs.ARCH)
    model, params = cs.build_model(cfg, 0)
    plain, kw = cs.serve_phase(model, params, cfg, 0, "cpu")
    assert sorted(plain) == [0, 1, 2, 3]
    cs.spec_phase(model, params, cfg, 0, "cpu", kw, plain)
    err = cs.logits_phase(cfg, params, 0, prompt_len=32, steps=2, window=4)
    assert err <= cs.LOGIT_TOL


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_follows_the_env_var(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there."""
    code = ("from repro.launch.compile_cache import enable_compile_cache\n"
            "import jax, jax.numpy as jnp\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))"
            ".block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert any(name.startswith("jit_") for name in os.listdir(tmp_path))
