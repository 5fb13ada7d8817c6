"""Persistent XLA compilation-cache setup, shared by every entry point.

A kernel-group program costs seconds to tens of seconds to compile per
geometry; caching compiled executables on disk makes broker restarts,
benchmark runs and redeploys start warm. The directory is part of the cache
key, so it must never move: where ``JAX_COMPILATION_CACHE_DIR`` is set jax
reads it itself and no directory is set in code; otherwise the cache lives
at the fixed, git-ignored ``<checkout>/.xla_cache`` — the same path in every
process and run.
"""

from __future__ import annotations

import os
from pathlib import Path

#: <checkout>/.xla_cache — fixed: no host fingerprint, pid, time or temp name
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".xla_cache")


def enable_persistent_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
