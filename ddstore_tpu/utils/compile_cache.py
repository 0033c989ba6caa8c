"""Where compiled programs are kept between processes and runs.

A cold LM step compiles for tens of seconds; JAX's persistent compilation
cache turns that into a file read, but only when every process and every
run names the same directory. So the entry points (``chip_smoke.py``, the
training examples, ``benchmarks/run.py``) call :func:`enable_compile_cache`
once, before their first jit, and nothing else in the tree sets a cache
directory. The same call starts the program's count of what a cache does
not save, tracing and lowering
(:func:`ddstore_tpu.utils.profile.counters`).
"""

from __future__ import annotations

import os

from . import profile

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Returns the persistent compile-cache directory in force.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself and this
    function touches no config. Unset: ``<checkout>/.jax_cache`` — a fixed
    path, never derived from a temporary name, a pid or the clock, so that
    a second run finds what the first one compiled.

    Also starts ``profile.counters()``: the seconds spent tracing and
    lowering each jitted function, which no cache takes away."""
    profile.watch_compiles()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
