"""Persistent XLA compilation cache for the entry points.

Compile time is part of the cold-start budget (serving/cold_start.py's
``compile`` phase), so processes that start the same programs share one
on-disk cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it. Otherwise the cache lives at the
fixed ``<checkout>/.jax_cache``: a directory that moved between runs
would never hit.

Only entry points call ``enable_compile_cache`` (``launch/serve.py``'s
``main``, ``benchmarks/run.py``, ``chip_smoke.py``) — never at import and
never in tests.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]  # src/repro/launch/ -> checkout
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
