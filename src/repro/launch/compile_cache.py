"""JAX's persistent compilation cache, kept at one fixed place.

Called by entry points (``chip_smoke.py``, ``benchmarks/run.py``), never at
import.  A later run finds what an earlier one compiled only if both use
the same directory, so it never moves between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, so nothing is set here) and otherwise ``.jax_cache`` at the root of
this source checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
