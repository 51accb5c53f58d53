"""Persistent compilation cache setup, shared by every entry point.

A compiled program is keyed on its cache directory among other things, so
the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads that variable itself, and nothing here overrides it), otherwise
``.jax_cache/`` at the root of the checkout, which git ignores.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
