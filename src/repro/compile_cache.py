"""Persistent JAX compilation cache for the repo's entry points.

Called from ``chip_smoke.py`` and the benchmark and example scripts (never
when the library is imported).  A fixed path matters: the cache directory
is part of what a later run must find again, so it is never derived from a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache is ``<checkout>/
    .jax_cache`` (gitignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of compiled programs the cache directory holds (JAX writes
    one ``*-cache`` file per program, beside its ``*-atime`` stamp)."""
    p = Path(path)
    return len(list(p.glob("*-cache"))) if p.is_dir() else 0
