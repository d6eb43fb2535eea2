"""Where JAX's persistent compilation cache lives (one rule for every
entry point: ``chip_smoke.py`` and the benchmark scripts).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
  here sets another directory.
* Unset: the cache goes to ``<checkout>/.jax_cache/`` — a fixed path
  (listed in ``.gitignore``), never a temp dir, pid or timestamp, so a
  later process on the same checkout finds the programs again.

Called explicitly by entry points; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
