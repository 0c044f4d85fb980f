"""Where compiled programs and autotune winners persist between runs.

Both live under one fixed directory of the checkout (``.cache/``, ignored
by git): the persistent compilation cache is keyed by its path, so a
directory that moved between runs would never hit.  Nothing here runs at
import; the entry points call :func:`configure_compile_cache` first.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/cache.py`` -> three levels up)
ROOT = Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".cache"
#: JAX's persistent compilation cache, unless the environment names one
COMPILE_CACHE_DIR = CACHE_DIR / "jax"
#: on-device autotune sweep winners (``kernels/autotune.py``)
AUTOTUNE_CACHE_PATH = CACHE_DIR / "autotune.json"

ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.
    """
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
