"""Where compiled programs are kept between processes.

One rule, applied by every entry point (``train.py``, ``bench.py``,
``chip_smoke.py``, the on-chip scripts, the multi-process test workers):

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it by itself; the code
  sets no other directory, so whoever launches the program places the
  cache (and a machine that keeps that directory between calls keeps the
  compiled programs).
* otherwise — ``<checkout>/.jax_cache``, resolved from this file. The
  directory is part of nothing's identity: no temp name, pid, user name
  or time, so two processes of one checkout always meet in it.
"""

import os

import jax

__all__ = ["CACHE_ENV", "enable", "entries"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def entries(path: str) -> int:
    """Number of compiled programs stored under ``path`` (0 when the
    directory does not exist yet)."""
    if not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
