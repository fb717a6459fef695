"""JAX's persistent compilation cache, placed from outside the library.

The command-line entry points (``python -m repro.launch.simulate``,
``chip_smoke.py``, ``python -m benchmarks.run``) call
:func:`enable_compile_cache` once before they compile anything; nothing
calls it at import or from ``simulate.main()`` itself, so library users
and the tests, which call ``main()`` in process, keep JAX's own default
(no cache unless they set one).

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise ``.jax_cache/`` at the root of the checkout.  The path is
part of each entry's key, so it is fixed: never built from a temporary
name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> Path:
    """Where the persistent compilation cache lives (see module doc)."""
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    return CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and cache every compiled program, however
    quick its compile.  Returns the directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
