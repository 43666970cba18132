"""JAX's persistent compilation cache for the launchers and chip_smoke.py.

Call :func:`enable_compile_cache` before the first compilation: JAX reads
the setting once, when it first compiles.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the cache only hits where the path
# stays the same from one run to the next (listed in .gitignore).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself), and ``<checkout>/.jax_cache`` otherwise.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
