"""JAX's persistent compilation cache, kept at one fixed path per checkout.

A cache hits only when the directory is the same from run to run, so the
path is fixed: never a temp dir, a pid or a time.  Entry points call
``enable_compile_cache()`` first thing in ``main()``; nothing here runs at
import (repro-lint's global-state rule).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it: the
    setting is left alone.  Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
