"""Where the entry points keep JAX's persistent compilation cache.

A full-width model pays seconds to minutes of XLA compilation per program
shape, and a fresh machine starts with none of it. Entry points (the
serving launcher, the examples, the benchmark runner, ``chip_smoke.py``)
call :func:`use_compile_cache` once, before they compile anything:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets nothing;
* otherwise the cache goes to ``<repo>/.jax_cache`` (git-ignored). The
  path is fixed, because it is part of what a later run looks up.

Library modules and tests never call it: importing ``repro`` leaves JAX's
configuration as it found it.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    where = os.environ.get(ENV_VAR)
    if where:
        return where
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
