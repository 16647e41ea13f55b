"""Where JAX's persistent compilation cache lives for the command-line tools.

Every static signature of the simulator (policy, geometry, timing pack,
refresh mode, trace length, ...) compiles its own program, so a cold run
pays every compile again. The entry points (``benchmarks.run``, the
``examples/`` mains and ``chip_smoke.py``) call :func:`enable` first, so a
second run in the same checkout loads those programs instead.

* Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself: the
  cache stays there and no path is set here.
* Otherwise the cache goes to :data:`CACHE_DIR`, ``.jax_cache`` at the root
  of the checkout. The path is fixed (it is part of the cache's key), so a
  later run in the same checkout finds what an earlier one wrote.
* Either way every program is cached, not only those that took JAX's
  default minimum of one second to compile: a sweep compiles dozens of
  programs of about that size, one per static signature.

Library code and the test suite never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``).
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Switch the persistent cache on for this process; return its path."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
