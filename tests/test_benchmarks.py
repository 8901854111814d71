"""Tier-1 collects the benchmark's own tests (``benchmarks/tests/``).

``pytest.ini`` collects ``tests/`` alone, so each case of each
``benchmarks/tests/test_*.py`` is re-exported here under
``test_<file>__<case>`` (parametrised cases and fixtures keep working: the
marks live on the function objects) and counts as a tier-1 test of its own.
They stay runnable by hand as before:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests``.
"""

import importlib.util
import pathlib
import sys

BENCH_TESTS = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "tests"
sys.path.insert(0, str(BENCH_TESTS))       # their ``_paths`` helper
import _paths  # noqa: E402,F401  (puts benchmarks/ and the repo on sys.path)

for _path in sorted(BENCH_TESTS.glob("test_*.py")):
    _spec = importlib.util.spec_from_file_location(
        "benchmarks_tests_" + _path.stem, _path)
    _module = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_module)
    for _name, _obj in list(vars(_module).items()):
        if _name.startswith("test_") and callable(_obj):
            globals()[f"{_path.stem}__{_name[len('test_'):]}"] = _obj
        elif hasattr(_obj, "_fixture_function_marker"):
            globals()[_name] = _obj
