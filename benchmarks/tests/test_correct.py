"""What decides ``correct``, at the rehearsal's size on the CPU: the plain
reference agrees with the program, the float8 control does not, and a run
whose timed path is broken underneath comes out as not correct."""

import json
import os
import time

import pytest

from _paths import ROOT

CELL = "olmo1b-train-b4s2048"


def _resolved():
    import run as harness
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return harness.resolve(manifest, CELL, rehearse=True)  # no JAX config


def _run(tmp_path, seed, wrap_step=None):
    """The rest of a run, without the harness's look for a chip."""
    import jax
    from runners import train
    if jax.device_count() != 1:
        pytest.skip("the one-chip cell rehearses on one CPU device")
    cell, cfg = _resolved()
    return train.run(cell, cfg, {
        "seed": seed, "seconds": 0.2, "trace": False, "rehearse": True,
        "t_start": time.perf_counter(), "scratch": str(tmp_path)},
        wrap_step=wrap_step)


def test_sound_run_is_correct_and_prints_each_number_beside_its_limit(
        tmp_path):
    record = _run(tmp_path, seed=2 ** 31 + 5)
    assert record["correct"] is True
    assert set(record["compared"]) == {
        "loss1_gap", "loss2_gap", "grad_gap", "change_gap", "grad_dir_gap",
        "grad_dir_gap_median", "change_dir_gap_median", "compiles_in_window",
        "nonfinite_losses"}
    for row in record["compared"].values():
        assert row["value"] <= row["limit"]
    assert record["window"]["steps"] > 0 and record["window"]["compiles"] == 0
    assert record["attempted"] == record["window"]["steps"] + 3  # warm-up


def _state_unchanged(compiled, ses):
    import jax
    import jax.numpy as jnp

    def step(state, batch):
        _, metrics = compiled(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return step


def _half_batch(compiled, ses):
    """Half of the rows left out, the mean taken over the rest: the
    program's own step with a mask over the targets of the first half."""
    import jax.numpy as jnp

    from dtdl_tpu.train import make_lm_train_step
    masked = make_lm_train_step(ses.strategy)

    def step(state, batch):
        rows, row_tokens = batch["tokens"].shape
        mask = (jnp.arange(rows) < rows // 2).astype(jnp.float32)
        mask = jnp.broadcast_to(mask[:, None], (rows, row_tokens - 1))
        return masked(state, dict(batch, mask=mask))
    return step


@pytest.mark.parametrize("fault, failing", [
    (_state_unchanged, "change_gap"), (_half_batch, "grad_gap")])
def test_broken_timed_path_is_not_correct(tmp_path, fault, failing):
    record = _run(tmp_path, seed=77, wrap_step=fault)
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
    for fault in correct.FAULTS:
        nums = correct.numbers(
            correct.reference_readings(*args, fault=fault), refr)
        ok, table = correct.judge(nums, cell["limits"])
        assert not ok, (fault, table)
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
