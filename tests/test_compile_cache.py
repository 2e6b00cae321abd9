"""The persistent compile cache lives in one fixed directory.

``$JAX_COMPILATION_CACHE_DIR`` when it is set, else ``<checkout>/.jax_cache``
(``repro.launch.compile_cache``).  The cache-writing case runs in a child
process so this process's JAX configuration is left alone.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]

_CHILD = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0)).block_until_ready()
"""


def _listing(path: Path) -> set:
    return set(path.rglob("*")) if path.exists() else set()


def test_default_is_the_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_the_only_one_written(tmp_path):
    cache = tmp_path / "cache"
    before = _listing(compile_cache.DEFAULT_DIR)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=tmp_path, check=True)
    assert out.stdout.strip().splitlines()[-1] == str(cache)
    assert any(p.is_file() for p in cache.rglob("*"))
    assert _listing(compile_cache.DEFAULT_DIR) == before
