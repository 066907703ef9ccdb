"""Persistent XLA compilation cache for the entry points.

A cold start compiles every step function of the model, which at
published widths takes minutes on the chip.  JAX can keep compiled
programs on disk and find them again on the next start, but only if the
directory is the same each time: the path is part of what makes a hit.

``enable_compile_cache()`` is called first thing by ``chip_smoke.py``,
``repro.launch.serve`` and ``repro.launch.train``:

  * if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
    cache lives there — nothing else is configured;
  * otherwise the cache goes to ``.jax_cache/`` at the root of the
    checkout (listed in ``.gitignore``), never a temporary, per-process or
    per-run directory.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
