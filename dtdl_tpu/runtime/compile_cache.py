"""Where the persistent XLA compilation cache lives.

Cold compiles dominate a short run (the 'large' LM train step alone is
about a minute on a v5e), and every process pays them again unless the
cache is on disk.  The location is a deployment setting, so it is placed
from OUTSIDE first: when ``JAX_COMPILATION_CACHE_DIR`` is in the
environment jax reads it itself and this module touches nothing.
Otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed path
derived from this file, never a temp dir, a pid or the time, because the
path is part of what makes a later process find the entries again.

Entry points call :func:`enable_compile_cache` once, before the first
compile (``examples/common.py:bootstrap``, ``chip_smoke.py``,
``bench.py``); nothing else in the repo sets a cache directory.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory used."""
    if ENV_VAR in os.environ:
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
