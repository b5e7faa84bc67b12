"""Persistent XLA compilation cache.

Tile processes are short-lived relative to XLA compile times (the batched
ed25519 verify graph takes tens of seconds to compile for a TPU and
minutes on the CPU backend), so every entry point that jits device code
enables the on-disk cache: first boot pays, every later process loads.
The reference has no analogue — its compile cost is `make` — but this is
the same role as its build cache.

Where the cache lives: `JAX_COMPILATION_CACHE_DIR` when the environment
sets it (JAX reads that variable itself, and no other directory is set
here), else `<checkout>/.xla_cache`.  The path is part of a cache entry's
key, so it must not move between runs.
"""

import os

_enabled = False

_ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """The cache directory enable() uses — also the home of cache-adjacent
    artifacts like the test suite's PRIMED sentinel."""
    env = os.environ.get(_ENV)
    if env:
        return env
    return os.path.normpath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..", ".xla_cache"))


def enable():
    """Turn the persistent cache on for this process.

    FDTPU_XLA_CACHE_READONLY=1 reads cache entries but never WRITES them:
    this jaxlib's executable serialization segfaults sporadically on large
    CPU executables, so tile processes on the CPU (disco/run.py) read
    only; the process that owns the chip writes."""
    global _enabled
    if _enabled:
        return
    import jax

    if not os.environ.get(_ENV):
        path = cache_dir()
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    if os.environ.get("FDTPU_XLA_CACHE_READONLY"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          1e9)
    else:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _enabled = True
