"""What decides ``correct``, at the rehearsal's size on the CPU: the plain
reference agrees with the program, the float8 control does not, and a run
whose timed path is broken underneath comes out as not correct: once for
each fault a cell can have, on one CPU device and on four."""

import json
import os
import subprocess
import sys

import pytest

from _paths import BENCH, ROOT

CELL = "olmo1b-train-b4s2048"
DDP_CELL = "olmo1b-train-ddp4"


def _resolved():
    import run as harness
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return harness.resolve(manifest, CELL, rehearse=True)  # no JAX config


def _run(cell, seed, fault=None):
    """The rest of a run of a cell's rehearsal, without the harness's look
    for a chip, in a process of its own (``_faults.py``): it takes as many
    CPU devices as the cell has chips, whatever this process holds."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "_faults.py"), cell,
         str(seed)] + ([fault] if fault else []),
        env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [CELL, DDP_CELL])
def test_sound_run_is_correct_and_prints_each_number_beside_its_limit(cell):
    record = _run(cell, seed=2 ** 31 + 5)
    assert record["correct"] is True
    assert set(record["compared"]) == {
        "loss1_gap", "loss2_gap", "grad_gap", "change_gap", "grad_dir_gap",
        "grad_dir_gap_median", "change_dir_gap_median", "compiles_in_window",
        "nonfinite_losses"}
    for row in record["compared"].values():
        assert row["value"] <= row["limit"]
    assert record["window"]["steps"] > 0 and record["window"]["compiles"] == 0
    assert record["attempted"] == record["window"]["steps"] + 3  # warm-up


@pytest.mark.parametrize("cell, fault, failing", [
    (CELL, "state_unchanged", "change_gap"), (CELL, "half_batch", "grad_gap"),
    (DDP_CELL, "state_unchanged", "change_gap"),
    (DDP_CELL, "half_batch", "grad_gap"),
    (DDP_CELL, "exchange_left_out", "grad_gap")])
def test_broken_timed_path_is_not_correct(cell, fault, failing):
    record = _run(cell, seed=77, fault=fault)
    assert record["correct"] is False
    row = record["compared"][failing]
    assert row["value"] > row["limit"], record["compared"]


@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 13])
def test_float8_control_is_not_correct_and_bf16_witness_is(seed):
    from lib import correct
    from runners import train
    cell, cfg = _resolved()
    shapes = train.make_plan(cell, cfg).shapes
    args = (cfg, shapes, seed, cell["batch_per_chip"], cell["row_tokens"],
            "uniform", cell["optimizer"]["lr"])
    refr = correct.reference_readings(*args)
    control = correct.reference_readings(*args, precision="fp8")
    ok, table = correct.judge(correct.numbers(control, refr), cell["limits"])
    assert not ok, table
    witness = correct.reference_readings(*args, precision="bf16")
    ok, table = correct.judge(correct.numbers(witness, refr), cell["limits"])
    assert ok, table


def test_planted_faults_in_the_reference_fail_too():
    from lib import correct
    from runners import train
    cell, cfg = _resolved()
    shapes = train.make_plan(cell, cfg).shapes
    args = (cfg, shapes, 5, cell["batch_per_chip"], cell["row_tokens"],
            "uniform", cell["optimizer"]["lr"])
    refr = correct.reference_readings(*args)
    assert correct.faults_of(1) == ("half_batch", "state_unchanged")
    for fault in correct.faults_of(4):
        nums = correct.numbers(
            correct.reference_readings(*args, fault=fault, chips=4), refr)
        ok, table = correct.judge(nums, cell["limits"])
        assert not ok, (fault, table)
    with pytest.raises(ValueError, match="exchange_left_out"):
        correct.reference_readings(*args, fault="exchange_left_out")
    unchanged = correct.numbers(correct.reference_readings(
        *args, fault="state_unchanged"), refr)
    assert unchanged["change_gap"] == pytest.approx(1.0, abs=1e-4)


def test_dead_leaves_are_left_out_of_the_change_by_the_reference_gradient():
    from lib import correct

    def side(norms):
        return {"norm": dict(norms),
                "proj": {k: [n] * correct.PROJECTIONS
                         for k, n in norms.items()}}
    grad = {"a": 1.0, "b": 1.0, "c": 1e-9}
    refr = {"loss": [1.0] * correct.STEPS, "grad": side(grad),
            "change": side({"a": 1.0, "b": 1.0, "c": 1e-6})}
    prog = json.loads(json.dumps(refr))
    prog["change"]["norm"]["c"] = 5e-5   # round-off under Adam: not compared
    nums = correct.numbers(prog, refr)
    assert nums["leaves_compared"] == 2 and nums["change_gap"] == 0.0
    prog["change"]["norm"]["a"] = 2.0    # a leaf moved double reads 1
    assert correct.numbers(prog, refr)["change_gap"] == pytest.approx(1.0)
    prog["grad"]["proj"]["b"] = [0.9] * correct.PROJECTIONS
    nums = correct.numbers(prog, refr)
    assert nums["grad_dir_gap"] == pytest.approx(0.1)
    assert nums["grad_dir_gap_leaf"] == "b" and nums["grad_gap"] == 0.0
    assert nums["grad_dir_gap_median"] == 0.0


def test_signed_sums_estimate_the_norm_of_the_difference():
    import jax
    import numpy as np
    from lib import correct, weights
    key = weights.seed_key(3)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 257))
    noise = 0.05 * jax.random.normal(jax.random.PRNGKey(2), x.shape)
    readings = correct.leaf_readings
    read = readings({"w": x, "other": x}, key)
    noisy = readings({"w": x + noise}, key)       # the same path's signs
    proj = {k: np.asarray(v) for k, v in read["proj"].items()}
    assert proj["w"].shape == (correct.PROJECTIONS,)
    apart = np.sqrt(np.mean((np.asarray(noisy["proj"]["w"]) - proj["w"]) ** 2))
    assert apart / float(read["norm"]["w"]) == pytest.approx(0.05, rel=0.4)
    # another path draws other signs; the norm does not care
    assert not np.allclose(proj["other"], proj["w"])
    assert float(read["norm"]["other"]) == float(read["norm"]["w"])
