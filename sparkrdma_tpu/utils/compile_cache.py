"""Where the persistent XLA compilation cache lives.

A cold fused step at TeraSort scale compiles for ~40 s on the XLA:TPU
compiler and replays from the cache in under a second, so every entry
point that compiles (``chip_smoke.py``, ``bench.py``, the CLI demos,
``__graft_entry__.py``) calls ``enable_compile_cache()`` before its
first compile. The directory is part of the cache key, so it must not
move between runs: it is either the one the operator placed through
``JAX_COMPILATION_CACHE_DIR`` (jax reads that variable itself; nothing
is set in code then) or ``<checkout>/.jax_cache``, resolved from this
package's own path — never from the working directory, a temp name, a
pid or the time.
"""

from __future__ import annotations

import os

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
