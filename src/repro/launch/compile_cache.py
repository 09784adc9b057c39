"""JAX's persistent compilation cache: where it lives and how it is turned on.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads
the variable itself, so nothing here sets a directory then), and otherwise
``<checkout>/.jax_cache``. The path is fixed because it is part of the
cache's key: a directory that moved would never hit. The size and
compile-time thresholds are zeroed so every executable persists, the small
ones of a CPU run included (DESIGN.md §15).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> Path:
    """The directory the persistent compilation cache uses."""
    env = os.environ.get(ENV)
    return Path(env) if env else DEFAULT_DIR


def cache_entries(path: Path | None = None) -> int:
    """Number of executables stored in the cache directory (0 if absent)."""
    path = cache_dir() if path is None else path
    return len(list(path.glob("*-cache"))) if path.is_dir() else 0


def enable_compilation_cache() -> Path:
    """Turn the persistent compilation cache on before the first compile;
    returns its directory."""
    import jax

    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir()
