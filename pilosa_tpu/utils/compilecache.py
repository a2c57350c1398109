"""Persistent XLA compilation cache placement.

Every entry point that initializes JAX (``Server``, and through it the
CLI's ``server`` command; the worker executor; ``chip_smoke.py``'s
kernels child) calls :func:`enable` before its first jit, so all
processes of a deployment share one on-disk cache and a restarted
server skips the compiles its predecessor already paid.

The directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself) wins when
the operator or the chip tool sets it; otherwise the cache lives at the
fixed, gitignored ``<checkout>/.jax_cache``.
"""
import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable():
    """Place the persistent compilation cache; return its directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # JAX's default keeps only programs that took over a second to
    # compile. On a v5e the served path's programs take 0.1-2 s each
    # and there are dozens of them (chip_smoke.py: 13 compiles in 5.7 s
    # left one cache entry), so keep them all.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
