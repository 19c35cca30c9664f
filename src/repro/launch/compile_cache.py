"""Persistent XLA compilation cache for the launch entry points.

The launchers and ``chip_smoke.py`` call :func:`enable_compile_cache`
before their first compile; importing the package never does, so tests
and library users keep JAX's defaults.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (this file is ``src/repro/launch/compile_cache.py``)
_CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    the variable itself, and nothing else is set here).  Otherwise the
    cache lives at the fixed ``.jax_cache/`` of the checkout root, so
    every run from the same checkout finds what an earlier one compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["enable_compile_cache"]
