"""Persistent JAX compilation cache for the command-line entry points.

Called from the ``main()`` of each entry point (never at import, never
from tests), so a second run of the same program loads its executables
instead of recompiling them. ``JAX_COMPILATION_CACHE_DIR``, when set,
is the only cache path: JAX reads it itself and nothing is set here.
Otherwise the cache lives at the fixed ``<repo root>/.jax_cache`` (the
path is part of what makes a later run hit), which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn the persistent compilation cache on."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
