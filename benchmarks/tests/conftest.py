"""Tests of the benchmark itself: CPU, fast, no topology call at import.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Not part of tier-1 (``pytest.ini`` collects ``tests/`` alone, and this PR
may add no file there).
"""

import jax

import _paths  # noqa: F401  (puts the benchmark and the repo on sys.path)

jax.config.update("jax_platforms", "cpu")
