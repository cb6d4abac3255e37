"""Persistent XLA compile cache, placed from outside.

A cold process compiles every program it runs (the full-width CNN span
alone is tens of seconds for a v5e), and a machine with a chip may be
new for every command. JAX's persistent compilation cache removes the
repeat cost, provided every process of a run — and the next run on the
same disk — looks in the same directory: the directory is part of the
cache key, so a path that moves never hits.

Placement rule, in one function so no entry point can drift:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
  set in code, so whoever runs the program decides where the cache
  lives (and whether it survives the machine).
- otherwise: ``<checkout>/.jax_cache``, derived from this package's
  ``__file__`` — fixed for a checkout, never a temp dir, a pid or a
  timestamp. ``.gitignore`` lists it.

Called first thing by ``ddl_tpu.cli.main``, ``bench.py``, every
``benchmarks/*.py`` main and ``chip_smoke.py``. The test suite does not
call it (tests compile tiny programs, and the described-topology
compiles of ``tests/test_chip_compile.py`` cannot read their own
entries back without a chip).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.jax_cache`` — the parent of the ``ddl_tpu`` package."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable() -> str:
    """Turn the persistent compile cache on and return its directory.
    Must run before the process's first compilation."""
    import jax

    # Cache every program, not only those that took >= 1 s to compile:
    # a serve run is dozens of sub-second bucket programs.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get(ENV_VAR)
    if not path:
        path = default_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    return path
