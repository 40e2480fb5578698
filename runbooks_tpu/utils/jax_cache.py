"""Persistent JAX compilation cache, placed from outside.

A restarted trainer Job or serve worker — and every cold call on a chip
machine — otherwise pays the full XLA compile again while the chips idle.

Where it lives is the deployment's decision, not the workload's:

  JAX_COMPILATION_CACHE_DIR   set: JAX reads it itself; this module sets no
                              directory in code. The operator exports it to
                              the durable artifacts mount for train Jobs
                              (controller/model.py).
  (unset)                     <checkout>/.jax_cache, one fixed git-ignored
                              path. The directory is part of the cache key,
                              so it is never derived from a temp dir, a pid
                              or the clock.
  RBT_JAX_CACHE=0             disable entirely (the test suite does, so
                              tier-1 neither writes into the checkout nor
                              depends on what a previous run left there).

Call before the process's first compile: JAX decides once, at the first
compile, whether a cache is in use.
"""

from __future__ import annotations

import os
from typing import Optional

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> Optional[str]:
    """Turn the persistent compilation cache on and return its directory
    (None only under RBT_JAX_CACHE=0). Errors propagate: a cache that
    silently fails costs minutes of chip time per start."""
    if os.environ.get("RBT_JAX_CACHE") == "0":
        return None
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache every compile that takes noticeable time: the default 1 s floor
    # skips the many small serve/trainer helper jits whose compiles still
    # add up across a restart.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    return cache_dir
