"""Where compiled programs persist between runs: one directory, placed
from outside the program.

Entry points (``chip_smoke.py``, the launchers, the benchmark scripts)
call :func:`enable_compile_cache` from ``main()``, never at import.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the path is part of what
makes a later run find an entry again.  Git ignores that directory.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory compiled programs go to: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on at
    :func:`compile_cache_dir`; returns that directory."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return compile_cache_dir()
