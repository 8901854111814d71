"""``run.py`` as the driver runs it: the last line's keys, on one and on
four virtual CPU devices (DDP as data: a test cell and the benchmark's own
``olmo1b-train-ddp4``), a cell of a second family (``tests/data/``: its
family file, configuration, cell and manifest, and no file outside knows of
them), and its refusals."""

import json
import os
import subprocess
import sys

import pytest

from _paths import BENCH, ROOT

DATA = os.path.join(BENCH, "tests", "data")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(*args, cwd=ROOT, env=None):
    env = dict(env or os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("extra, cell, chips", [
    ([], "olmo1b-train-b4s2048", 1),
    (["--manifest", os.path.join(DATA, "BENCHMARK.ddp4.json"),
      "--data", DATA], "test-ddp4", 4),
    ([], "olmo1b-train-ddp4", 4),
    (["--manifest", os.path.join(DATA, "BENCHMARK.toy.json"),
      "--data", DATA], "test-toy", 1)])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(extra, cell, chips, trace):
    done = _run("--workload", cell, "--seed", str(2 ** 31 + 99),
                "--seconds", "0.5", "--trace", str(trace), "--rehearse",
                *extra)
    assert done.returncode == 0, done.stderr[-2000:]
    out = done.stdout.strip().splitlines()
    assert any(line.startswith("REHEARSAL on cpu") for line in out)
    line = json.loads(out[-1])
    assert list(line)[:5] == KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert "memory_peak_bytes" in line["device"]
    if trace:
        # on the CPU there is no device line: the trace's readers find
        # nothing to read and their metrics are left out, never 0
        assert "step.host_dispatch_ms" in line["metrics"]
        assert "flash_roofline" not in line["metrics"]
        assert "step_mfu" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    err = done.stderr.strip().splitlines()
    assert err[-1] == "correct: True"
    assert any(ln.startswith("compared grad_gap:") and " limit " in ln
               for ln in err)


def test_no_chip_is_an_error_and_prints_no_result():
    done = _run("--workload", "olmo1b-train-b4s2048", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "needs 1 TPU chip" in done.stderr
    assert not done.stdout.strip()


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns(
        "__pycache__", ".scratch"))
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "olmo1b-train-b4s2048", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and not done.stdout.strip()
    assert "dtdl_tpu" in done.stderr


def test_unknown_workload_is_refused():
    done = _run("--workload", "no-such-cell", "--seed", "1", "--seconds",
                "1", "--trace", "0", "--rehearse")
    assert done.returncode != 0 and "not in the manifest" in done.stderr
