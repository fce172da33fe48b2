"""JAX's persistent compilation cache, set in one place.

Entry points (``chip_smoke.py``, the ``train`` and ``serve`` mains) call
:func:`enable_compile_cache` once at start-up; nothing sets it at import.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
itself and this module sets no other. Otherwise the cache lives in
``.jax_cache/`` at the repository root: a fixed path, because the path
is part of what makes a later run find the entries again."""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
