"""JAX persistent compilation cache placement for the entry points.

``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` from their
``main``; importing this module changes nothing. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and this module sets no other directory. Otherwise the cache lives at
``<checkout>/.jax_cache`` (gitignored): a fixed path, because the path is
part of what the cache matches on, so a directory that moved would never
hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache(root: os.PathLike | str | None = None) -> str:
    """Point JAX's compilation cache at its fixed place; returns the
    directory in use. ``root`` is the checkout holding ``.jax_cache``
    (default: the one this module lives in)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(root or CHECKOUT) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
