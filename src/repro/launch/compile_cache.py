"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve_gbdt``, the
benchmarks and the examples) call :func:`enable_compile_cache` before
they compile anything, so a second run of the same program on the same
chip reads its executables back instead of compiling them again.
Nothing calls it at import, and tests never do.
"""

from __future__ import annotations

import os
import pathlib

import jax

# A fixed directory inside the checkout: the cache path is part of what
# makes an entry found again, so it never comes from a temp name, a
# process id or the time.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and the
    choice is left to JAX.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
