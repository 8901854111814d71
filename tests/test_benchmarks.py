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

# a benchmark test that imports another by its plain name
# (``from test_kimi_linear import CATALOG``) leaves it in ``sys.modules``
# under a name a file of tests/ has too, and pytest then refuses to collect
# that file ("import file mismatch"): the name is given back
for _name in [p.stem for p in BENCH_TESTS.glob("test_*.py")]:
    _module = sys.modules.get(_name)
    if _module is not None and pathlib.Path(
            getattr(_module, "__file__", "") or "").parent == BENCH_TESTS:
        del sys.modules[_name]


def _qwen3_next_manifest_entries_by_name():
    """``benchmarks/tests/test_qwen3_next.py`` asks that the Qwen3-Next cell
    and configuration be the *last* entries of ``BENCHMARK.json``'s lists,
    and that its four metrics list that cell alone.  They did when PR 29
    wrote it; PR 33 appended another cell, the grouped-matmul metrics
    (``moe_gmm.*``) now list the JoyAI-LLM-Flash cell too (whose five expert
    layers run the same kernels), and a PR that adds to the benchmark may
    not edit a file the benchmark has.  So the case is run here with the
    entries found by name and the second cell of ``moe_gmm.*`` named
    (everything else it asserts is kept); the file's own lines are a
    `benchmark` PR's to repair (PERF.md section 7)."""
    import json
    import os

    from _paths import ROOT
    module = _MODULES["test_qwen3_next"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = {m["name"]: m for m in manifest["per_layer"]
            if module.CELL in m.get("workloads", ())}
    assert sorted(mine) == sorted(module.READERS)
    assert {name: m["workloads"] for name, m in mine.items()} == {
        "gdn.ms": [module.CELL], "gdn_roofline": [module.CELL],
        "moe_gmm.ms": [module.CELL, "joyaiflash-train-share32"],
        "moe_gmm_roofline": [module.CELL, "joyaiflash-train-share32"]}
    assert {m["layer"] for m in mine.values()} == {"linear attention",
                                                   "experts"}
    assert all(m["moves"] == "train_tokens_per_s" for m in mine.values())
    cell, = [w for w in manifest["workloads"] if w["name"] == module.CELL]
    assert cell["chips"] == 1
    config, = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert config["file"] == os.path.relpath(module.CONFIG, ROOT)


test_qwen3_next__manifest_lists_the_cell_for_its_four_metrics_alone = \
    _qwen3_next_manifest_entries_by_name


def _setup_manifest_entries_by_layer():
    """``benchmarks/tests/test_program_names.py`` asks that the entries that
    move ``setup_s`` be the compile account's four alone.  PR 37 added a
    fifth, ``setup.gc_s`` of the layer ``host`` (the collector's pauses),
    and a PR that adds to the benchmark may not edit a file the benchmark
    has.  So the case is run here with the account's entries found by their
    layer (everything else it asserts is kept) and the fifth pinned beside
    them; the file's own line is a `benchmark` PR's to repair (PERF.md
    section 7)."""
    import json
    import os

    from _paths import BENCH, ROOT
    module = _MODULES["test_program_names"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = [m for m in manifest["per_layer"] if m["moves"] == "setup_s"]
    account = [m for m in entries if m["layer"] == "runtime / compile"]
    assert [m["name"] for m in account] == list(module.SETUP_READERS)
    assert [(m["name"], m["layer"]) for m in entries if m not in account] \
        == [("setup.gc_s", "host")]
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in entries:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert os.path.isfile(
            os.path.join(BENCH, "metrics", m["name"] + ".py"))
        assert (m["moves"], m["source"]) == ("setup_s", "program_counter")
        assert m["layer"] in perf and m["name"] in perf


test_program_names__manifest_entries_of_the_setup_metrics = \
    _setup_manifest_entries_by_layer


def _kimi_linear_manifest_entries_with_the_host_metrics():
    """``benchmarks/tests/test_kimi_linear.py`` counts 13 metrics without a
    list of cells, as PR 33 left them; PR 37 appended three more of that
    kind (the ``host`` layer's), which every cell reports, the Kimi-Linear
    cell among them, and then one more, ``flash.bwd_ms`` (the flash
    backward whichever implementation ran).  The case is run here with the
    count taken as the 13 and ``flash.bwd_ms``, and the three (everything
    else it asserts is kept); the file's own line is a `benchmark` PR's to
    repair (PERF.md section 7)."""
    import json
    import os

    from _paths import ROOT
    module = _MODULES["test_kimi_linear"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m.get("workloads") == [module.CELL]}
    assert sorted(mine) == sorted(module.READERS)
    assert {m["layer"] for m in mine.values()} == {"linear attention"}
    assert all(m["moves"] == "train_tokens_per_s" and
               m["source"] == "device_trace" for m in mine.values())
    cell, = [w for w in manifest["workloads"] if w["name"] == module.CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "train-b2s4096"
    config, = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert config["file"] == os.path.relpath(module.CONFIG, ROOT)
    unlisted = [m for m in manifest["per_layer"] if "workloads" not in m]
    assert [m["name"] for m in unlisted if m["layer"] != "host"][13:] == [
        "flash.bwd_ms"]
    assert [m["name"] for m in unlisted if m["layer"] == "host"] == [
        "setup.gc_s", "host.gc_ms", "host.gc_longest_ms"]


test_kimi_linear__manifest_lists_the_cell_for_its_two_metrics = \
    _kimi_linear_manifest_entries_with_the_host_metrics


def _rehearsal_line_with_the_host_metrics(extra, cell, chips, trace):
    """``benchmarks/tests/test_run_line.py`` asks every metric of a
    rehearsed line to read above 0.  Since PR 37 a traced line carries the
    ``host`` layer's readings of the collector, and a window in which no
    collection fell reads ``host.gc_ms`` and ``host.gc_longest_ms`` 0.0: an
    honest reading, not a missing one.  The case is run here with those two
    allowed to read 0 and all three required in a traced line (everything
    else it asserts is kept); the file's own line is a `benchmark` PR's to
    repair (PERF.md section 7)."""
    import json

    module = _MODULES["test_run_line"]
    done = module._run("--workload", cell, "--seed", str(2 ** 31 + 99),
                       "--seconds", "0.5", "--trace", str(trace),
                       "--rehearse", *extra)
    assert done.returncode == 0, done.stderr[-2000:]
    out = done.stdout.strip().splitlines()
    assert any(line.startswith("REHEARSAL on cpu") for line in out)
    line = json.loads(out[-1])
    assert list(line)[:5] == module.KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert "memory_peak_bytes" in line["device"]
    may_be_zero = {"host.gc_ms", "host.gc_longest_ms"}
    if trace:
        assert "step.host_dispatch_ms" in line["metrics"]
        assert "flash_roofline" not in line["metrics"]
        assert "step_mfu" not in line["metrics"]
        if "--manifest" not in extra:
            assert {"setup.gc_s"} | may_be_zero <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert m["value"] > 0 or (name in may_be_zero and m["value"] == 0)
    err = done.stderr.strip().splitlines()
    assert err[-1] == "correct: True"
    assert any(ln.startswith("compared grad_gap:") and " limit " in ln
               for ln in err)


_rehearsal_line_with_the_host_metrics.pytestmark = _MODULES[
    "test_run_line"].test_rehearsal_prints_the_contracts_last_line.pytestmark
test_run_line__rehearsal_prints_the_contracts_last_line = \
    _rehearsal_line_with_the_host_metrics


def _host_manifest_entries_by_name():
    """``benchmarks/tests/test_host_pauses.py`` asks that the ``host``
    layer's three be the *last* entries of ``per_layer``.  The
    multi-token-prediction reader ``mtp.ms`` was appended behind them, and
    a PR that adds to the benchmark may not edit a file the benchmark has.
    So the case is run here with the three found by name, in their order
    (everything else it asserts is kept); the file's own line is a
    `benchmark` PR's to repair (PERF.md section 7)."""
    import json
    import os

    module = _MODULES["test_host_pauses"]
    with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    moves = {"setup.gc_s": ("s", "setup_s"),
             "host.gc_ms": ("ms", "train_tokens_per_s"),
             "host.gc_longest_ms": ("ms", "train_tokens_per_s")}
    assert [m["name"] for m in manifest["per_layer"]
            if m["layer"] == "host"] == list(module.HOST_READERS)
    for name, (unit, moved) in moves.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "host", "moves": moved}
        assert os.path.isfile(os.path.join(_paths.BENCH, "metrics",
                                           name + ".py"))
    with open(os.path.join(_paths.ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(name in perf for name in module.HOST_READERS)


test_host_pauses__manifest_entries_of_the_host_metrics = \
    _host_manifest_entries_by_name
