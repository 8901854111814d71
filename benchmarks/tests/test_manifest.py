"""BENCHMARK.json against the files it names and the contract's limits."""

import json
import os
import re

import pytest

from _paths import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def test_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks"]
    assert manifest["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_names_units_and_lines(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in manifest[group]]
        assert len(seen) == len(set(seen)), group
        names += seen
    for w in manifest["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in manifest["configs"]:
        names += c["reduced"]
        assert len(c["reduced"]) <= 16 and 1 <= len(c["why"]) <= 200
    assert all(NAME.match(n) for n in names), names
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES


def test_configs_name_files_that_state_their_cut(manifest):
    widths = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                        r"_dim$|_rank$|head_dim|experts_per_tok")
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmarks/configs/")
        cfg = _load(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert not [k for k in c["reduced"] if widths.search(k)]
        for key in c["reduced"]:
            assert key in cfg["published"], (c["name"], key)
            assert cfg["published"][key] != cfg[key]
        for key in ("assumed", "departures", "deployment", "precision",
                    "memory"):
            assert key in cfg, (c["name"], key)
        assert any(w["config"] == c["name"] for w in manifest["workloads"])


def test_cells_name_config_traffic_and_runner_files(manifest):
    configs = {c["name"] for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert w["config"] in configs
        cell = _load(os.path.join(BENCH, "workloads", w["name"] + ".json"))
        for key in ("config", "traffic", "chips"):
            assert cell[key] == w[key], (w["name"], key)
        assert os.path.isfile(
            os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(
            os.path.join(BENCH, "runners", cell["runner"] + ".py"))
        assert set(cell["limits"]) >= {"grad_gap", "change_gap"}
        for name, limit in cell["limits"].items():
            # every limit stands between the two readings it was set from:
            # the upper one is the least of the control's (3x the lower or
            # more), the half-batch fault's (10x), a state left
            # unchanged's (3x) and, across chips, the left-out exchange's
            # (10x)
            read = cell["limits_from"][name]
            lower = read["lower"]
            uppers = [read[key] for key, times in (
                ("control_fp8_min", 3), ("half_batch_min", 10),
                ("state_unchanged", 3), ("exchange_left_out_min", 10))
                if key in read and read[key] >= times * lower]
            assert read["upper"] == min(uppers), (w["name"], name)
            assert read["lower_runs"] >= 12
            assert read["control_seeds"] >= 3 and read["half_batch_seeds"] >= 3
            if w["chips"] > 1:
                assert read["exchange_left_out_seeds"] >= 3
            assert lower < limit < read["upper"], (w["name"], name)
            assert limit / lower >= read["upper"] / limit, (w["name"], name)
        # the control fails one of the cell's numbers, and so does each fault
        for reading in ("control_fp8_min", "half_batch_min",
                        "state_unchanged") + (
                ("exchange_left_out_min",) if w["chips"] > 1 else ()):
            assert any(cell["limits_from"][n].get(reading, 0) > limit
                       for n, limit in cell["limits"].items()), reading
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(manifest["workloads"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_metrics_have_readers_and_move_what_their_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(
            os.path.join(BENCH, "metrics", m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e
        assert reported_in(m) <= reported_in(e2e[m["moves"]])
        assert reported_in(m) <= set(cells)
        layers.add(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's layers do not list {layer!r}"
    assert any("mfu" in re.split(r"[._]", m["name"])
               for m in manifest["per_layer"])
    for cell in cells:
        assert any(cell in reported_in(m) for m in manifest["per_layer"])
        assert sum(cell in reported_in(m) for m in e2e.values()) >= 2
