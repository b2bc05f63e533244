"""Persistent XLA compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``serve_decoder.main``,
``benchmarks/run.py``) call :func:`enable_compile_cache` in ``main()``
before the first compile, and after any ``jax.distributed.initialize``
(the helper reads the backend); importing the library never touches the
cache. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the helper
leaves the directory alone. Otherwise, on the TPU, the cache lives at one
fixed path in the checkout, ``<repo>/.jax_cache`` (git-ignored): the path
is part of what a later process must find again, so it is never built
from a tempdir, a pid or a time. On the CPU backend the helper places no
cache: XLA:CPU reloads its cached executables with a warning that the
compiling machine's features may not match the host's.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

#: The in-checkout cache directory used when the environment names none.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# Pallas kernels compile in about a second on the chip; JAX's default floor
# (1 s) would leave the fastest of them uncached.
_MIN_COMPILE_SECS = 0.1


def enable_compile_cache() -> str | None:
    """Turn on the persistent compilation cache; return its directory
    (``None`` where no cache is in use)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() != "tpu":
            return None
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_SECS)
    return path
