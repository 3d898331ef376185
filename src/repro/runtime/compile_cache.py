"""Where JAX's persistent compilation cache lives.

One rule for every process of this repository (the chip smoke script, the
benchmark harness, shard servers): when ``JAX_COMPILATION_CACHE_DIR`` is set
the caller placed the cache and JAX reads the variable itself, so no
directory is set in code; otherwise the cache goes to one fixed, git-ignored
directory inside the checkout.  A fixed path matters because the directory
is part of what a later process must find again: a per-run, per-pid or
temp-dir path never hits.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program: a respawned shard server's first-call kernels are
    # small and fast to compile, but a respawn pays all of them at once.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
