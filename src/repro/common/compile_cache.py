"""JAX's persistent compilation cache, placed for the program's entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call ``enable_compile_cache()`` once at start; importing this module changes
nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
the directory is left to it; otherwise the cache lives at one fixed path
inside the checkout, ``experiments/jax_cache/`` (gitignored), so a second
run in the same checkout finds what the first compiled.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), "experiments", "jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Every program is cached, however fast it compiled (JAX's default skips
    those under a second), so a warm run compiles only what is new."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
