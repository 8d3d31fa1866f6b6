"""The one place the persistent XLA compilation cache is set up.

Rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
into its config and this module sets no directory of its own; where it
is not, the cache is ``<checkout>/.jax_cache`` (git-ignored). The path is
part of the cache key's surroundings — a temporary name, a pid or a time
in it would mean a cache that never hits — so it is fixed per checkout.
Every program is cached (no compile-time or size floor): a restart
replays the small programs too.

Called by every process that compiles: the CLI (``_apply_backend_flags``),
the replica worker, ``chip_smoke.py``, ``benchmark/run.py`` and
``tests/conftest.py``. A cache is never an input: nothing reads it except
JAX, and deleting it only costs compile time.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process, before its first
    compile; returns the directory JAX will use."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
