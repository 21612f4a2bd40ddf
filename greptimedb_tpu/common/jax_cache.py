"""Persistent XLA compilation cache for server processes.

The first jit compile of each kernel family takes seconds on the TPU; a
restarted server (or a fresh maintenance-job process) would pay it
again. JAX ships a persistent on-disk cache, and the cache directory is
part of every entry's key, so a directory that moves never hits. The
directory is therefore placed from outside with
`JAX_COMPILATION_CACHE_DIR` (JAX reads it itself; no code here touches
the setting then) and is otherwise one fixed path in the checkout —
never under a data_home, a temp dir, a pid or a timestamp.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger(__name__)

#: <checkout>/.jax_cache (git-ignored)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns the directory
    in use. Safe to call before or after backend init."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        # the server still answers, every restart recompiles
        logger.warning("compile cache directory %s unusable: %s",
                       cache_dir, e)
    # cache everything that took XLA real work; tiny kernels skip
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
