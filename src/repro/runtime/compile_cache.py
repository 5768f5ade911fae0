"""Persistent compilation cache placement for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call :func:`use_compile_cache` once, before their first compile; importing
``repro`` never does, so library users keep whatever cache they chose.

* ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it; nothing is
  overridden, so a cache placed from outside is the one that fills.
* unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path,
  because the directory is part of what a later run must find again
  (no temporary name, pid or time in it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

#: the cache directory when the environment names none
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
