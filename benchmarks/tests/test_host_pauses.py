"""The ``host`` readers (PR 37): ``setup.gc_s``, ``host.gc_ms`` and
``host.gc_longest_ms`` on hand-built pause rows and records, the program
that keeps no such rows, the set-up readers beside them, the manifest's
entries, and a rehearsed traced line that carries all three."""

import collections
import json
import os
import subprocess
import sys

import pytest

from _paths import BENCH, ROOT

HOST_READERS = ("setup.gc_s", "host.gc_ms", "host.gc_longest_ms")
SETUP_READERS = ("setup.trace_lower_s", "setup.compile_load_s",
                 "setup.other_programs_s", "setup.cache_hit_pct")


def _reader(name):
    import run as harness
    return harness.load_module("metrics", name).read


def _pauses(monkeypatch, spans):
    """The program's ring holding one pause a ``(start, stop, generation)``."""
    from dtdl_tpu.runtime import compile_cache
    rows = [compile_cache.GcPause("gc", None, stop, stop - start, gen)
            for start, stop, gen in spans]
    monkeypatch.setattr(compile_cache, "gc_pauses", lambda: list(rows))
    return rows


def _record(start=100.0, seconds=10.0, steps=4):
    return {"window": {"start": start, "seconds": seconds, "steps": steps}}


def _read(record):
    return {name: _reader(name)(record) for name in HOST_READERS}


def test_no_pause_rows_read_nothing(monkeypatch):
    from dtdl_tpu.runtime import compile_cache
    _pauses(monkeypatch, [])                    # a ring no collection reached
    assert _read(_record()) == dict.fromkeys(HOST_READERS)
    monkeypatch.delattr(compile_cache, "gc_pauses")     # as the parent
    assert _read(_record()) == dict.fromkeys(HOST_READERS)


@pytest.mark.parametrize("record", [
    {}, {"window": {"steps": 3}}, {"window": {"start": 100.0, "steps": 3}}],
    ids=["no_window", "no_start", "no_seconds"])
def test_a_record_without_its_window_reads_nothing_in_it(monkeypatch, record):
    _pauses(monkeypatch, [(99.0, 99.5, 0)])
    got = _read(record)
    assert got["host.gc_ms"] is None and got["host.gc_longest_ms"] is None
    assert got["setup.gc_s"] == (0.5 if "start" in record.get("window", {})
                                 else None)


def test_none_in_the_interval_reads_zero(monkeypatch):
    _pauses(monkeypatch, [(120.0, 120.5, 2)])   # after the window
    assert _read(_record()) == {"setup.gc_s": 0.0, "host.gc_ms": 0.0,
                                "host.gc_longest_ms": 0.0}
    _pauses(monkeypatch, [(90.0, 91.0, 0), (111.0, 112.0, 2)])
    got = _read(_record())
    assert got["setup.gc_s"] == pytest.approx(1.0)
    assert got["host.gc_ms"] == 0.0 and got["host.gc_longest_ms"] == 0.0
    assert _reader("host.gc_ms")(_record(steps=0)) is None


def test_a_pause_across_the_windows_start_is_split(monkeypatch):
    _pauses(monkeypatch, [(99.25, 100.5, 2)])
    got = _read(_record(steps=4))
    assert got["setup.gc_s"] == pytest.approx(0.75)
    assert got["host.gc_ms"] == pytest.approx(1e3 * 0.5 / 4)
    assert got["host.gc_longest_ms"] == pytest.approx(500.0)
    # and across its end: cut there too
    _pauses(monkeypatch, [(109.5, 111.0, 2)])
    got = _read(_record(steps=4))
    assert got["host.gc_ms"] == pytest.approx(1e3 * 0.5 / 4)
    assert got["host.gc_longest_ms"] == pytest.approx(500.0)


def test_nested_and_overlapping_pauses_count_once(monkeypatch):
    _pauses(monkeypatch, [
        (10.0, 12.0, 2), (10.5, 11.0, 0),       # nested
        (11.5, 13.0, 1),                        # overlapping
        (101.0, 101.25, 0), (101.1, 101.2, 0),  # nested, in the window
        (102.0, 103.0, 2), (102.5, 103.5, 0),   # overlapping, in the window
    ])
    got = _read(_record(steps=5))
    assert got["setup.gc_s"] == pytest.approx(3.0)
    assert got["host.gc_ms"] == pytest.approx(1e3 * (0.25 + 1.5) / 5)
    assert got["host.gc_longest_ms"] == pytest.approx(1e3)


def test_setup_reads_nothing_where_the_ring_may_have_rolled_out(monkeypatch):
    from dtdl_tpu.runtime import compile_cache
    _pauses(monkeypatch, [(1.0 + i, 1.5 + i, 0) for i in range(4)])
    monkeypatch.setattr(compile_cache, "GC_RING", 4)
    got = _read(_record(start=3.0))
    assert got["setup.gc_s"] is None
    assert got["host.gc_ms"] == pytest.approx(1e3 * 1.0 / 4)
    monkeypatch.setattr(compile_cache, "GC_RING", 5)
    assert _reader("setup.gc_s")(_record(start=3.0)) == pytest.approx(1.0)


def test_the_setup_readers_read_the_same_beside_pause_rows(monkeypatch):
    """The compile account's four readers read ``compile_account()`` alone:
    the pause rows, in the program's own ring, change none of them."""
    from dtdl_tpu.runtime import compile_cache
    events = {v: k for k, v in compile_cache.ACCOUNT_EVENTS.items()}
    Row = compile_cache.CompileRow
    rows = [
        Row(events["compile_trace_s"], "lm_train_step", 102.0, 2.0),
        Row(events["compile_lower_s"], "jit(lm_train_step)", 104.0, 1.5),
        Row(events["compile_cache_hits"], None, 105.0, 1),
        Row(events["compile_backend_s"], "jit(lm_train_step)", 105.5, 1.25),
        Row(events["compile_trace_s"], "readings", 106.0, 0.5),
        Row(events["compile_backend_s"], "jit(readings)", 108.0, 2.0),
        Row(events["compile_cache_misses"], None, 108.0, 1),
    ]
    monkeypatch.setattr(compile_cache, "compile_account", lambda: list(rows))
    record = _record(start=120.0)
    _pauses(monkeypatch, [])
    without = {name: _reader(name)(record) for name in SETUP_READERS}
    _pauses(monkeypatch, [(101.0, 101.5, 2), (103.0, 103.25, 0),
                          (107.0, 107.1, 1), (121.0, 121.5, 2)])
    beside = {name: _reader(name)(record) for name in SETUP_READERS}
    assert beside == without
    assert without == {"setup.trace_lower_s": pytest.approx(3.5),
                       "setup.compile_load_s": pytest.approx(1.25),
                       "setup.other_programs_s": pytest.approx(2.5),
                       "setup.cache_hit_pct": pytest.approx(50.0)}
    assert _reader("setup.gc_s")(record) == pytest.approx(0.85)


def test_manifest_entries_of_the_host_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    moves = {"setup.gc_s": ("s", "setup_s"),
             "host.gc_ms": ("ms", "train_tokens_per_s"),
             "host.gc_longest_ms": ("ms", "train_tokens_per_s")}
    assert [m["name"] for m in manifest["per_layer"]][-3:] == list(
        HOST_READERS)
    for name, (unit, moved) in moves.items():
        # no "workloads" key: every cell reports them
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "host", "moves": moved}
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(name in perf for name in HOST_READERS)


def test_rehearsed_traced_line_carries_the_host_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "olmo7b-train-b2s2048", "--seed", str(2 ** 31 + 37), "--seconds",
         "0.5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    metrics = line["metrics"]
    assert line["correct"] is True
    assert metrics["setup.gc_s"]["unit"] == "s"
    assert metrics["setup.gc_s"]["value"] > 0
    # the collector's set-up seconds lie inside the benchmark's set-up
    spans = line["notes"]["setup_spans_s"]
    assert metrics["setup.gc_s"]["value"] < sum(spans.values())
    for name in HOST_READERS[1:]:
        assert metrics[name]["unit"] == "ms" and metrics[name]["value"] >= 0
    assert (metrics["host.gc_longest_ms"]["value"]
            <= 1e3 * line["notes"]["window_s"])
