"""JAX's persistent compilation cache for the entry points.

``python -m repro.launch.train``, ``python -m repro.launch.serve``,
``benchmarks/run.py`` and ``chip_smoke.py`` call :func:`enable_compile_cache`
before their first compile, so a second run in the same checkout loads its
executables instead of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set here, so the cache lands where the environment says and nowhere else.
* unset: the cache goes to :data:`DEFAULT_DIR`, ``.jax_cache`` at the root
  of the checkout (listed in ``.gitignore``). The path is fixed — no pid,
  time or temp name — so the next run in the same checkout finds what this
  one wrote.

The test suite never calls this: tests compile small programs from scratch.
"""

from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
