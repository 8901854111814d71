"""What ``remat=True`` keeps: the checkpoint plan (models/remat_plan.py).

* **the plan as a pure function** — residual bytes from shapes, the ladder,
  the estimate of what a step holds under rung 0 against the two readings
  the chip gave (ledger, PRs 24 and 25), and the plan each benchmark cell's
  shapes get at the chip's limit;
* **the program** — on the tiny flash model under the Pallas interpreter,
  every rung trains as ``remat=False`` does, the lowered step loses exactly
  the recomputation its rung keeps, ``remat=False`` lowers as it did before
  there were names, and the plan reaches ``Observer.summary()``.

No device here reports a memory limit, so a test that wants a rung hands the
step the limit that buys it (``monkeypatch`` on ``device_bytes_limit``): the
program has no option to set.
"""

import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pathlib

import pytest

from dtdl_tpu.models import remat_plan, transformer as transformer_mod
from dtdl_tpu.models.transformer import TransformerLM
from dtdl_tpu.obs import Observer
from dtdl_tpu.obs.trace import device_component
from dtdl_tpu.ops import attention
from dtdl_tpu.parallel import AutoSharded, DataParallel, SingleDevice
from dtdl_tpu.resil import StepGuard
from dtdl_tpu.runtime import compile_cache
from dtdl_tpu.runtime.mesh import DATA_AXIS
from dtdl_tpu.train import make_lm_train_step
from dtdl_tpu.train.state import TrainState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E_LIMIT = 16_909_336_064      # memory_stats()["bytes_limit"], TPU v5 lite
GB = 1e9

# the benchmark's two cells and the chip's device.peak_hbm_gb in each under
# full recomputation (ledger, PR 24/25)
CELLS = {"olmo1b-train-b4s2048": 12.474, "olmo7b-train-b2s2048": 9.879}


def _cell(name):
    """The cell's traffic (rows and tokens a chip) and its configuration."""
    def load(*path):
        with open(os.path.join(REPO, "benchmarks", *path)) as f:
            return json.load(f)
    cell = load("workloads", name + ".json")
    return (load("traffic", cell["traffic"] + ".json"),
            load("configs", cell["config"] + ".json"))


def _cell_costs(name):
    cell, cfg = _cell(name)
    one = remat_plan.residual_bytes(
        cell["batch_per_chip"], cell["row_tokens"] - 1, cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["intermediate_size"], 2)
    return [one] * cfg["num_hidden_layers"]


# ---------------------------------------------------------------------------
# the plan as a pure function
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name, flash, qkv, out, mlp, whole", [
    ("olmo1b-train-b4s2048", 0.27, 0.80, 0.27, 2.15, 3.49),
    ("olmo7b-train-b2s2048", 0.07, 0.20, 0.07, 0.36, 0.70),
])
def test_residual_bytes_are_the_issues_table(name, flash, qkv, out, mlp,
                                             whole):
    costs = _cell_costs(name)
    per_rung = [sum(c[r] for c in costs) / GB for r in range(3)]
    assert per_rung[0] == pytest.approx(flash, abs=0.005)
    # rung 2 is q k v (three quarters of it) and the out projection's output
    assert per_rung[1] * 3 / 4 == pytest.approx(qkv, abs=0.01)
    assert per_rung[1] / 4 == pytest.approx(out, abs=0.005)
    assert per_rung[2] == pytest.approx(mlp, abs=0.005)
    assert sum(per_rung) == pytest.approx(whole, abs=0.005)
    # exactly: o + f32 lse, four [T, d] values, wi + wg, in bf16
    cell, cfg = _cell(name)
    b, s = cell["batch_per_chip"], cell["row_tokens"] - 1
    d, h, f = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["intermediate_size"])
    assert costs[0] == (b * s * d * 2 + b * h * s * 4, 4 * b * s * d * 2,
                        2 * b * s * f * 2)


_MOE_MIX = [(10, 40, 100), (10, 40, 0)] * 3     # every other block is MoE


@pytest.mark.parametrize("costs", [
    [(10, 40, 100)] * 8, _MOE_MIX, [(7, 3, 5)] * 2, []],
    ids=["dense8", "moe_mix", "two_blocks", "no_blocks"])
def test_ladder_is_monotone_in_the_budget_and_never_over_it(costs):
    whole = sum(map(sum, costs))
    before = (0,) * len(costs)
    for budget in range(0, whole + 12):
        rungs, kept = remat_plan.ladder(costs, budget)
        assert kept <= budget
        assert kept == sum(sum(c[:r]) for c, r in zip(costs, rungs))
        assert all(a <= b for a, b in zip(before, rungs)), (budget, rungs)
        # a ladder: no block stands more than a rung above a later dense one
        dense = [r for c, r in zip(costs, rungs) if c[2]]
        assert dense == sorted(dense, reverse=True)
        assert not dense or dense[0] - dense[-1] <= 1
        before = rungs
    assert kept == whole
    assert rungs == tuple(3 if c[2] else 2 for c in costs)


def test_ladder_order_is_flash_then_projections_then_mlp():
    costs = [(10, 40, 100)] * 4
    assert remat_plan.ladder(costs, 0) == ((0, 0, 0, 0), 0)
    assert remat_plan.ladder(costs, 25) == ((1, 1, 0, 0), 20)
    assert remat_plan.ladder(costs, 40 + 40) == ((2, 1, 1, 1), 80)
    assert remat_plan.ladder(costs, 200 + 250) == ((3, 3, 2, 2), 400)
    assert remat_plan.RUNGS == ("recompute", "flash", "attn_proj", "mlp")
    assert remat_plan.saved_names(0) == ()
    assert remat_plan.saved_names(1) == (attention.FLASH_OUT,)
    assert remat_plan.saved_names(3) == (
        attention.FLASH_OUT, attention.FLASH_QKV, remat_plan.ATTN_OUT,
        remat_plan.MLP_UP)
    assert remat_plan.policy(0) is None and callable(remat_plan.policy(2))


@pytest.mark.parametrize("linear, names", [
    (False, ("flash_out", "flash_qkv", "attn_out")),
    (True, ("gdn_loop", "gdn_in")), ("linear", ("gdn_loop", "gdn_in")),
    ("kda", ("kda_loop", "kda_in"))], ids=["full_or_mla", "gdn_bool",
                                           "gdn", "kda"])
def test_each_kind_of_block_has_a_ladder_of_its_own(linear, names):
    """A full and a latent-attention block keep the attention names; the
    two linear-attention kinds two rungs of their own, each under its own
    names (the names are where the code puts them: the rule's file and the
    layer)."""
    from dtdl_tpu.ops import gated_delta
    assert remat_plan.saved_names(2, linear=linear) == names
    assert remat_plan.saved_names(1, linear=linear, held=True) == (
        names[0], remat_plan.MOE_PLAN)
    assert remat_plan.saved_names(0, linear=linear) == ()
    assert (gated_delta.GDN_LOOP, remat_plan.GDN_IN, gated_delta.KDA_LOOP,
            remat_plan.KDA_IN) == ("gdn_loop", "gdn_in", "kda_loop", "kda_in")
    src = pathlib.Path(remat_plan.__file__).parents[1]
    layer = (src / "models" / "transformer.py").read_text()
    rule = (src / "ops" / "gated_delta.py").read_text()
    assert "remat_plan.KDA_IN" in layer and "KDA_LOOP)" in rule
    assert "remat_plan.GDN_IN" in layer and "GDN_LOOP)" in rule


@pytest.mark.parametrize("step", [
    None, ("lm_train_step", 0, None), ("lm_train_step", 10**12, V5E_LIMIT),
    # (iii) the end of the backward pass alone does not fit: the start
    # would leave a budget that buys every rung, and the plan keeps nothing
    ("lm_train_step", 0, V5E_LIMIT, V5E_LIMIT)],
    ids=["no_step", "no_limit", "nothing_left", "end_alone_over"])
def test_unknown_limit_or_no_step_or_no_room_is_rung_zero(step):
    costs = _cell_costs("olmo7b-train-b2s2048")
    model = remat_plan.ModelHeld(123, 45, 6)
    if step is None:
        plan = remat_plan.plan_checkpoints(costs, model, [7, 7])
        assert plan.fun_name is None and plan.estimate_bytes == 123
    else:
        with remat_plan.step_memory(*step):
            plan = remat_plan.plan_checkpoints(costs, model, [7, 7])
        assert plan.fun_name == "lm_train_step"
        assert plan.estimate_bytes == step[1] + 123
    assert plan.rungs == (0, 0) and plan.kept_bytes == plan.budget_bytes == 0
    if step is None or len(step) == 3:      # no gradient outlives its block
        assert plan.end_bytes is None and plan.walk_bytes is None
    else:
        assert plan.end_bytes == plan.walk_bytes == V5E_LIMIT + 45
        assert int(V5E_LIMIT * 15 / 16) - plan.estimate_bytes > sum(
            map(sum, costs))
    # and the context is gone once its body is left
    assert remat_plan.plan_checkpoints(costs, model, [7, 7]).fun_name is None


def test_device_limit_is_none_here_and_whole_64_mib_on_a_chip(monkeypatch):
    assert remat_plan.device_bytes_limit() is None      # the CPU reports none

    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    # two v5e hosts (one chip, four chips) as the chip runs read them
    for limit in (V5E_LIMIT, 16_909_334_528):
        monkeypatch.setattr(jax, "local_devices",
                            lambda limit=limit: [Chip({"bytes_limit": limit})])
        assert remat_plan.device_bytes_limit() == 251 * 2**26
    monkeypatch.setattr(jax, "local_devices", lambda: [Chip(None)])
    assert remat_plan.device_bytes_limit() is None


def test_what_the_step_holds_beside_the_model():
    leaves = [400, 100, 50]
    held = remat_plan.step_held_bytes
    assert held(1650, leaves, False, 1000, 0) == (1650, None)
    # gradients read together: none when the backward pass begins, all of
    # them when it ends (they were added to the one sum before PR 34)
    assert held(1650, leaves, True, 1000, 0) == (1650, 1650 + 550)
    # the chunked loss: four f32 [tokens, chunk] tiles and the table's grad
    # as the backward pass begins; by its end the tiles are gone
    assert held(1650, leaves, False, 1000, 8).start == (
        1650 + 4 * 1000 * 8 * 4 + 400)
    assert held(1650, leaves, True, 1000, 8) == (
        1650 + 4 * 1000 * 8 * 4 + 400, 1650 + 550)
    dense = remat_plan.model_held_bytes(4, 100, 64, 256, 2, 1000, 4000, 2)
    hidden = remat_plan.model_held_bytes(4, 100, 64, 256, 2, 0, 4000, 2)
    assert dense.start - hidden.start == 400 * 1000 * (4 + 2)   # logits + cotangent
    assert hidden.start == (2000 + 2 * 400 * 64 * 2
                            + 400 * (6 * 256 + 8 * 64) * 2)
    # at the end: the parameters' copy and one block's live set, no input
    assert dense.end == hidden.end == 2000 + 400 * (6 * 256 + 8 * 64) * 2
    assert dense.block_input == 400 * 64 * 2
    state = {"a": jax.ShapeDtypeStruct((3, 5), jnp.float32),
             "b": jax.ShapeDtypeStruct((7,), jnp.bfloat16), "n": 3}
    assert remat_plan.tree_bytes(state) == 60 + 14


def _traced_plan(monkeypatch, model, rows, row_tokens, limit, strategy=None,
                 **step_kwargs):
    """The plan the real step records when it is traced (nothing compiles)
    for ``model`` at these shapes on a device of ``limit`` bytes."""
    monkeypatch.setattr(remat_plan, "device_bytes_limit", lambda: limit)
    strategy = strategy or SingleDevice()
    rows *= strategy.num_replicas
    state = jax.eval_shape(
        lambda k: TrainState.create(
            apply_fn=model.apply, tx=optax.adamw(3e-4),
            params=model.init(k, jnp.zeros((1, row_tokens - 1), jnp.int32))
            ["params"]), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((rows, row_tokens), jnp.int32)}
    before = len(compile_cache.remat_plans())
    jax.eval_shape(make_lm_train_step(strategy, **step_kwargs), state, batch)
    plans = compile_cache.remat_plans()[before:]
    assert len(plans) == 1, "one plan a traced step"
    return plans[0]


def _cell_model(name):
    """The cell's model as the benchmark's runner builds it
    (benchmarks/runners/train.py:make_plan), whatever its family."""
    import sys
    bench = os.path.join(REPO, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from lib import modules
    cell, cfg = _cell(name)
    family = modules.load_file(os.path.join(
        bench, "families", cfg["model_type"] + ".py"), "families")
    model = TransformerLM(dtype=jnp.bfloat16,
                          **family.model_kwargs(cfg, True))
    return model, cell["batch_per_chip"], cell["row_tokens"]


@pytest.mark.parametrize("name", list(CELLS))
def test_cells_estimate_is_within_a_tenth_of_the_chips_reading(
        name, monkeypatch):
    plan = _traced_plan(monkeypatch, *_cell_model(name), None)
    assert plan.rungs == (0,) * len(plan.rungs) and plan.limit_bytes is None
    assert plan.estimate_bytes / GB == pytest.approx(CELLS[name], rel=0.10)


@pytest.mark.parametrize("limit", [16 * 10**9, V5E_LIMIT],
                         ids=["16e9", "v5e_bytes_limit"])
@pytest.mark.parametrize("name", list(CELLS))
def test_cells_plan_at_the_chips_limit(name, limit, monkeypatch):
    plan = _traced_plan(monkeypatch, *_cell_model(name), limit)
    costs = _cell_costs(name)
    assert plan.fun_name == "lm_train_step" and plan.limit_bytes == limit
    assert plan.budget_bytes == int(limit * 15 / 16) - plan.estimate_bytes
    assert plan.kept_bytes <= plan.budget_bytes
    assert plan.kept_bytes == sum(sum(c[:r])
                                  for c, r in zip(costs, plan.rungs))
    if name.startswith("olmo7b"):       # holds all of it several times over
        assert plan.rungs == (3, 3)
        assert plan.kept_bytes == sum(map(sum, costs))
    else:                               # does not hold all of it
        assert min(plan.rungs) >= 2 and len(plan.rungs) == 8
        assert plan.estimate_bytes + plan.kept_bytes <= limit * 15 / 16


# rungs, kept, budget and estimate of the step each cell traces at a v5e's
# limit (PERF.md section 4).  The four one-chip cells' are what they were
# before the plan had a second instant (PR 33's tree reads the same bytes);
# the four-chip cell's step reads its gradients together and gets the
# one-chip plan of the same shapes (before PR 34: rungs (2,1,1,1,1,1,1,1),
# 0.407 GB kept of a 0.460 GB budget, an estimate of 15.332 GB that counted
# 2.56 GB of gradients beside the residuals they replace), and so does a
# guarded step on one device.
_OLMO1B = ((3, 3, 3, 3, 3, 3, 2, 2), 2_955_540_480, 3_019_701_240,
           12_771_853_320)
_READ_TOGETHER = (12_591_927_304, 13_801_556_488)       # end, walk
_PINNED = {
    "olmo1b-train-b4s2048": ("olmo1b-train-b4s2048", 1, False, _OLMO1B),
    "olmo7b-train-b2s2048": ("olmo7b-train-b2s2048", 1, False, (
        (3, 3), 696_962_560, 5_128_281_592, 10_663_272_968)),
    "qwen3next-train-share16": ("qwen3next-train-share16", 1, False, (
        (2, 2, 1, 1), 1_476_804_480, 1_494_105_784, 14_297_448_776)),
    "kimilinear-train-share32": ("kimilinear-train-share32", 1, False, (
        (2, 2, 1, 1, 1), 1_812_872_960, 2_012_564_216, 13_778_990_344)),
    # PR 35: the sum of the head's instant and a block's reads 16.476 GB and
    # leaves nothing; told apart 14.284 GB (model_held_bytes.start_apart)
    "trinitymini-train-share16": ("trinitymini-train-share16", 1, False, (
        (2, 1, 1, 1, 1), 1_285_397_248, 1_507_103_224, 14_284_451_336)),
    "olmo1b-train-b4s2048-zipf": ("olmo1b-train-b4s2048-zipf", 1, False,
                                  _OLMO1B),
    "olmo1b-train-ddp4": ("olmo1b-train-ddp4", 4, False, _OLMO1B),
    "olmo1b_one_chip_guarded": ("olmo1b-train-b4s2048", 1, True, _OLMO1B),
}


@pytest.mark.parametrize("case", list(_PINNED))
def test_each_cells_plan_at_the_v5e_limit_is_pinned(case, monkeypatch,
                                                    devices):
    name, chips, guarded, want = _PINNED[case]
    limit = V5E_LIMIT >> 26 << 26
    assert limit == 16_844_324_864
    strategy = None if chips == 1 else DataParallel(
        mesh=jax.sharding.Mesh(np.asarray(devices[:chips]), (DATA_AXIS,)))
    plan = _traced_plan(monkeypatch, *_cell_model(name), limit,
                        strategy, **({"guard": StepGuard()} if guarded else {}))
    assert (plan.rungs, plan.kept_bytes, plan.budget_bytes,
            plan.estimate_bytes) == want
    assert plan.limit_bytes == limit
    ceiling = int(limit * 15 / 16)
    assert plan.estimate_bytes + plan.kept_bytes <= ceiling
    if chips == 1 and not guarded:
        # no gradient outlives its block: the first instant alone
        assert plan.end_bytes is None and plan.walk_bytes is None
    else:
        # the second instant is reported, fits, and is what shapes say:
        # 12 B a parameter of state (and its counters), 4 of gradient, 2 of
        # bf16 copy, one block's live set; the walk between the two peaks
        # after blocks 7 and 6 (rung 2: they keep less than their
        # gradients take) and stays under the ceiling as well
        assert (plan.end_bytes, plan.walk_bytes) == _READ_TOGETHER
        assert plan.end_bytes < plan.walk_bytes <= ceiling
        n_params, t = 639_928_320, 4 * 2047
        live = t * (6 * 8192 + 8 * 2048) * 2
        assert 0 <= plan.end_bytes - (n_params * 18 + live) < 4096
        block = 4 * (4 * 2048 ** 2 + 3 * 2048 * 8192 + 2 * 2048)
        assert plan.walk_bytes == remat_plan.walk_bytes(
            plan.end_bytes, _cell_costs(name), plan.rungs, t * 2048 * 2,
            [block] * 8)


@pytest.mark.parametrize("grads, end_room, rungs, kept", [
    # every block's gradient takes what the block kept: the end is the peak
    ([150] * 4, 100, (3, 3, 3, 3), 600),
    # blocks keep 30 more than their gradients take: the walk stands 120
    # over the end before the first block is done; the ladder's last
    # residual (block 3's rung 3) goes and the peak is 90, before block 2
    ([120] * 4, 100, (3, 3, 3, 2), 500),
    # gradients of no size (a frozen model's): whatever is kept stands over
    # the end whole, so the plan keeps what the end leaves room for
    ([0] * 4, 100, (2, 1, 1, 1), 80),
    ([0] * 4, 39, (1, 1, 1, 0), 30),
    # uneven blocks: the walk's peak is a prefix sum (here before block 2,
    # the first blocks' 300 standing over the end), not the total's 50
    ([0, 0, 400, 150], 320, (3, 3, 3, 3), 600),
    ([0, 0, 400, 150], 200, (3, 2, 2, 2), 300),
], ids=["end_is_the_peak", "last_residual_back", "no_gradients",
        "almost_no_room", "uneven_fits", "uneven_back"])
def test_the_walk_between_the_instants_gives_residuals_back(
        grads, end_room, rungs, kept):
    """A start that would buy every rung and an end that fits alone: the
    plan is the richest ladder whose walk (walk_bytes) fits as well."""
    costs = [(10, 40, 100)] * 4
    ceiling = 15 * 10**6
    limit = ceiling * 16 // 15
    model = remat_plan.ModelHeld(ceiling - 600, ceiling - end_room, 0)
    with remat_plan.step_memory("lm_train_step", 0, limit, 0):
        plan = remat_plan.plan_checkpoints(costs, model, grads)
    assert plan.budget_bytes == 600 and plan.end_bytes == ceiling - end_room
    assert (plan.rungs, plan.kept_bytes) == (rungs, kept)
    assert plan.walk_bytes <= ceiling
    # it is a ladder (what some smaller budget buys), and the next richer
    # ladder's walk does not fit
    assert remat_plan.ladder(costs, kept) == (rungs, kept)
    if kept < 600:
        richer, _ = remat_plan.ladder(costs, kept + 100)
        assert richer != rungs and remat_plan.walk_bytes(
            plan.end_bytes, costs, richer, 0, grads) > ceiling
    # without an end (one device) the same start buys every rung
    with remat_plan.step_memory("lm_train_step", 0, limit):
        alone = remat_plan.plan_checkpoints(costs, model, grads)
    assert alone.rungs == (3, 3, 3, 3) and alone.walk_bytes is None


# ---------------------------------------------------------------------------
# the program, on the tiny flash model
# ---------------------------------------------------------------------------

def _tiny(remat=True, dtype=jnp.float32, n_layers=2, **kw):
    return TransformerLM(vocab_size=256, d_model=64, n_layers=n_layers,
                         n_heads=4, d_ff=128, max_seq=64, attn_impl="flash",
                         remat=remat, dtype=dtype, **kw)


_TINY_COSTS = [remat_plan.residual_bytes(2, 63, 64, 4, 128, 4)] * 2


def _limit_for(estimate, costs, rungs):
    """The least limit whose budget buys ``rungs`` (a ladder) exactly."""
    kept = sum(sum(c[:r]) for c, r in zip(costs, rungs))
    return math.ceil((estimate + kept) * 16 / 15) + 1


def _tiny_state(model, rows=2):
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (rows, 64)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.adamw(3e-4))
    return state, {"tokens": tokens}


def _tiny_step(monkeypatch, rungs, model=None, strategy=None,
               costs=_TINY_COSTS, **step_kwargs):
    """``(step, state, batch)`` of the tiny model, on a device whose limit
    buys ``rungs`` (None: a device that reports none)."""
    model = model or _tiny()
    strategy = strategy or SingleDevice()
    limit = None
    if rungs is not None:
        at_zero = _traced_plan(monkeypatch, model, 2, 64, None, strategy,
                               **step_kwargs)
        limit = _limit_for(at_zero.estimate_bytes, costs, rungs)
    monkeypatch.setattr(remat_plan, "device_bytes_limit", lambda: limit)
    state, batch = _tiny_state(model, 2 * strategy.num_replicas)
    return make_lm_train_step(strategy, **step_kwargs), state, batch


@pytest.fixture(scope="module")
def trained_without_remat():
    state, batch = _tiny_state(_tiny(remat=False))
    new, metrics = make_lm_train_step(SingleDevice())(state, batch)
    return jax.device_get((new.params, new.opt_state, metrics["loss"]))


@pytest.mark.parametrize("rungs", [
    None, (0, 0), (1, 1), (2, 1), (2, 2), (3, 2), (3, 3)],
    ids=lambda r: "no_limit" if r is None else "rungs_%d%d" % r)
def test_every_rung_trains_as_remat_false_does(rungs, monkeypatch,
                                               trained_without_remat):
    step, state, batch = _tiny_step(monkeypatch, rungs)
    new, metrics = step(state, batch)
    assert compile_cache.remat_plans()[-1].rungs == (rungs or (0, 0))
    params, opt_state, loss = trained_without_remat
    assert float(metrics["loss"]) == pytest.approx(float(loss), rel=1e-6)
    # the first gradient as the optimizer got it (Adam's mu), then the state
    for got, want in zip(jax.tree.leaves(new.opt_state),
                         jax.tree.leaves(opt_state)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)
    for got, want in zip(jax.tree.leaves(new.params),
                         jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)


_STACK = re.compile(r'"(jit\(lm_train_step\)/[^"]*)"')


def _passes(lowered):
    """``{component: {pass: set of blocks}}`` of the lowered step's matmuls
    and flash kernels, from the name stacks in its locations."""
    seen = {}
    for stack in set(_STACK.findall(lowered.as_text(debug_info=True))):
        names = stack.split("/")
        if names[-1] != "dot_general" and "flash_fwd" not in names:
            continue
        component, phase = device_component(stack)
        if "flash_fwd" in names:
            component = "flash_fwd"
        elif component == "attn_proj":
            component = names[names.index("attn") + 1]
        elif component == "mlp":
            component = names[names.index("mlp") + 1]
        block = next((n for n in names if n.startswith("block_")), None)
        seen.setdefault(component, {}).setdefault(phase, set()).add(block)
    return seen


# the rung at which a block stops running each of its matmuls a second time
_KEPT_FROM = {"flash_fwd": 1, "q": 2, "k": 2, "v": 2, "out": 2, "wi": 3,
              "wg": 3}


@pytest.mark.parametrize("rungs", [(0, 0), (1, 1), (2, 1), (3, 3)],
                         ids=lambda r: "rungs_%d%d" % r)
def test_lowered_step_drops_exactly_what_its_rung_keeps(rungs, monkeypatch):
    """Rung 0 holds two ``flash_fwd`` calls a layer, the flash rung one; at
    the top rung no ``q k v out wi wg`` matmul lies under
    ``rematted_computation``."""
    step, state, batch = _tiny_step(monkeypatch, rungs)
    seen = _passes(step.lower(state, batch))
    blocks = ("block_0", "block_1")
    for name, rung in _KEPT_FROM.items():
        again = {b for b, r in zip(blocks, rungs) if r < rung}
        assert seen[name]["forward"] == set(blocks), name
        assert seen[name].get("recompute", set()) == again, (name, rungs)
        assert ("backward" in seen[name]) == (name != "flash_fwd")
    # nothing reads wo's output again, whatever the rung
    assert set(seen["wo"]) == {"forward", "backward"}


def test_remat_false_lowers_to_the_same_text_with_and_without_names(
        monkeypatch):
    def lowered_text():
        state, batch = _tiny_state(_tiny(remat=False, dtype=jnp.bfloat16,
                                         n_layers=1))
        before = len(compile_cache.remat_plans())
        text = make_lm_train_step(SingleDevice()).lower(state, batch).as_text()
        assert len(compile_cache.remat_plans()) == before   # nothing planned
        return text

    def unnumbered(text):
        # private functions carry a counter in their symbol (@silu_191)
        return re.sub(r"(@[A-Za-z_]+)_\d+\b", r"\1", text)

    named = lowered_text()
    for module in (attention, transformer_mod):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    unnamed = lowered_text()
    assert unnumbered(unnamed) == unnumbered(named)
    assert unnamed.count("\n") == named.count("\n") > 500
    # serving and generate() never rematerialize: decode ignores the flag
    model = _tiny(remat=True)
    prompt = jnp.zeros((1, 4), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), prompt)
    before = len(compile_cache.remat_plans())
    cache = model.init_cache(1)
    model.apply({"params": variables["params"], "cache": cache}, prompt,
                decode=True, mutable=["cache"])
    assert len(compile_cache.remat_plans()) == before


def test_plan_is_in_the_account_and_in_the_observers_summary(monkeypatch):
    step, state, batch = _tiny_step(monkeypatch, (3, 2))
    before = len(compile_cache.remat_plans())
    step.lower(state, batch)
    plans = compile_cache.remat_plans()[before:]
    assert len(plans) == 1      # once a traced step
    plan = plans[0]
    assert plan.fun_name == "lm_train_step" and plan.rungs == (3, 2)
    assert plan.kept_bytes == sum(_TINY_COSTS[0]) + sum(_TINY_COSTS[1][:2])
    assert plan.kept_bytes <= plan.budget_bytes < plan.kept_bytes + 4
    assert plan.budget_bytes == (int(plan.limit_bytes * 15 / 16)
                                 - plan.estimate_bytes)
    want = {"remat_blocks_by_rung": [0, 0, 1, 1],
            "remat_kept_bytes": plan.kept_bytes,
            "remat_budget_bytes": plan.budget_bytes,
            "remat_estimate_bytes": plan.estimate_bytes}
    totals = compile_cache.compile_totals()
    summary = Observer().summary()
    assert {k: totals[k] for k in want} == want
    assert {k: summary[k] for k in want} == want
    # a step whose gradients no one reads together has no second instant
    assert "remat_end_bytes" not in totals
    step, state, batch = _tiny_step(monkeypatch, (3, 2), guard=StepGuard())
    step.lower(state, batch)
    plan, totals = compile_cache.remat_plans()[-1], compile_cache.compile_totals()
    assert plan.rungs == (3, 2) and plan.end_bytes <= plan.walk_bytes
    assert (totals["remat_end_bytes"], totals["remat_walk_bytes"]) == (
        plan.end_bytes, plan.walk_bytes)


@pytest.fixture(scope="module")
def two_devices(devices):
    return DataParallel(mesh=jax.sharding.Mesh(np.asarray(devices[:2]),
                                               (DATA_AXIS,)))


def test_ddp_plans_from_one_chips_shapes_and_gspmd_recomputes(
        monkeypatch, two_devices, devices):
    single = _traced_plan(monkeypatch, _tiny(), 2, 64, 10**9)
    ddp = _traced_plan(monkeypatch, _tiny(), 2, 64, 10**9, two_devices)
    # inside shard_map the step sees one chip's rows; gradients wait for
    # the all-reduce together, which no residual does: the start's account
    # is the one-device step's, and the parameters' bytes stand in the end's
    assert ddp.rungs == single.rungs == (3, 3)
    assert ddp.kept_bytes == single.kept_bytes
    state = _tiny_state(_tiny())[0]
    n_param_bytes = remat_plan.tree_bytes(state.params)
    assert ddp.estimate_bytes == single.estimate_bytes
    assert single.end_bytes is None
    live = 2 * 63 * (6 * 128 + 8 * 64) * 4
    assert ddp.end_bytes == (remat_plan.tree_bytes(state)
                             + 2 * n_param_bytes + live)    # f32: a whole copy
    assert ddp.end_bytes <= ddp.walk_bytes <= 10**9 * 15 / 16
    guarded = _traced_plan(monkeypatch, _tiny(), 2, 64, 10**9,
                           guard=StepGuard())
    assert guarded.estimate_bytes == ddp.estimate_bytes
    assert (guarded.end_bytes, guarded.walk_bytes) == (ddp.end_bytes,
                                                       ddp.walk_bytes)
    # GSPMD traces global shapes: no plan is made from them
    mesh = jax.sharding.Mesh(np.asarray(devices[:2]), (DATA_AXIS,))
    auto = _traced_plan(monkeypatch, _tiny(), 1, 64, 10**9,
                        AutoSharded(mesh=mesh))
    assert auto.rungs == (0, 0) and auto.limit_bytes is None

    # and the step runs at the top rung under shard_map
    step, state, batch = _tiny_step(monkeypatch, (3, 3),
                                    strategy=two_devices)
    state = two_devices.replicate(state)
    new, metrics = step(state, two_devices.shard_batch(batch))
    assert np.isfinite(float(metrics["loss"]))
    assert compile_cache.remat_plans()[-1].rungs == (3, 3)


def test_moe_blocks_keep_the_attention_names_only(monkeypatch):
    model = _tiny(n_experts=4, moe_every=2, dtype=jnp.float32)
    plan = _traced_plan(monkeypatch, model, 2, 64, 10**9)
    assert plan.rungs == (3, 2)         # block_1 is the MoE block
    assert plan.kept_bytes == sum(_TINY_COSTS[0]) + sum(_TINY_COSTS[1][:2])
    monkeypatch.setattr(remat_plan, "device_bytes_limit", lambda: 10**9)
    state, batch = _tiny_state(model)
    new, metrics = make_lm_train_step(SingleDevice())(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert "moe_aux_loss" in metrics


def test_chunked_loss_frees_the_logits_for_a_richer_plan(monkeypatch):
    model = _tiny(dtype=jnp.bfloat16)
    dense = _traced_plan(monkeypatch, model, 2, 64, None)
    chunked = _traced_plan(monkeypatch, model, 2, 64, None,
                           vocab_chunk_size=16)
    tokens = 2 * 63
    table = 256 * 64 * 4
    assert dense.estimate_bytes - chunked.estimate_bytes == (
        tokens * 256 * (4 + 2) - 4 * tokens * 16 * 4 - table)
    # at the limit that buys the dense head the flash rung, the chunked
    # loss gets further up the ladder
    costs = [remat_plan.residual_bytes(2, 63, 64, 4, 128, 2)] * 2
    limit = _limit_for(dense.estimate_bytes, costs, (1, 1))
    assert _traced_plan(monkeypatch, model, 2, 64, limit).rungs == (1, 1)
    richer = _traced_plan(monkeypatch, model, 2, 64, limit,
                          vocab_chunk_size=16)
    assert richer.rungs > (1, 1) and richer.kept_bytes > sum(
        c[0] for c in costs)


# ---------------------------------------------------------------------------
# the Trinity block (PR 35): windowed and full layers, a gate projection of
# its own, four norms
# ---------------------------------------------------------------------------

def test_the_trinity_blocks_bytes_are_what_shapes_say():
    """A windowed layer keeps what a full one keeps (the band changes the
    recomputation's cost, not the bytes); the gate's own projection joins
    rung 2; a dense FFN inside a hybrid stack has a third rung; the norms on
    the sublayers' outputs add four ``[tokens, d_model]`` values to the live
    set."""
    b, s, d, h, width = 2, 8191, 2048, 32, 4096
    t = b * s
    plain = remat_plan.residual_bytes(b, s, d, h, 0, 2, attn_width=width)
    gated = remat_plan.residual_bytes(b, s, d, h, 6144, 2, attn_width=width,
                                      own_gate=True)
    assert plain == (t * width * 2 + b * h * s * 4,
                     (3 * width + d) * t * 2, 0)
    assert gated == (plain[0], plain[1] + t * width * 2, 2 * t * 6144 * 2)
    live = remat_plan.hybrid_block_live_bytes
    assert live(b, s, d, 2, attn_width=width, post_norms=True) \
        - live(b, s, d, 2, attn_width=width) == 4 * t * d * 2


def test_the_head_and_the_blocks_are_told_apart_where_the_sum_leaves_nothing():
    """``start`` adds the logits to a block's live set; ``start_apart`` takes
    the larger.  The plan stands on the sum wherever that leaves a budget
    (every accepted cell: pinned above) and falls back where it leaves
    none."""
    held = remat_plan.model_held_bytes(2, 100, 64, 0, 3, 1000, 4000, 2,
                                       block_live_bytes=900_000)
    logits = 200 * 1000 * 6
    assert held.start - held.start_apart == min(logits, 900_000)
    dense = remat_plan.model_held_bytes(4, 100, 64, 256, 2, 1000, 4000, 2)
    assert dense.start - dense.start_apart == 400 * (6 * 256 + 8 * 64) * 2
    costs = [(10_000, 40_000, 0)] * 3
    limit = 16 * (held.start + 31_000) // 15
    with remat_plan.step_memory("lm_train_step", 0, limit):
        roomy = remat_plan.plan_checkpoints(costs, held, [0] * 3)
    assert roomy.estimate_bytes == held.start and roomy.rungs == (1, 1, 1)
    limit = 16 * (held.start_apart + 71_000) // 15
    with remat_plan.step_memory("lm_train_step", 0, limit):
        apart = remat_plan.plan_checkpoints(costs, held, [0] * 3)
        none = remat_plan.plan_checkpoints(
            costs, held._replace(start_apart=None), [0] * 3)
    assert apart.estimate_bytes == held.start_apart
    assert apart.rungs == (2, 1, 1) and apart.kept_bytes == 70_000
    assert none.rungs == (0, 0, 0) and none.estimate_bytes == held.start


def test_the_trinity_cells_estimate_against_the_compilers_total(monkeypatch):
    """The plan the cell's step traces at the v5e limit against the bytes
    the v5e compiler gave that step (``tools/topology_compile.py``, recorded
    in the configuration's ``memory``): the estimate (what the step holds
    with nothing kept) stands over the compiler's total (with what the plan
    keeps), by no more than 3 GB, and the total under the ceiling."""
    _, cfg = _cell("trinitymini-train-share16")
    limit = V5E_LIMIT >> 26 << 26
    plan = _traced_plan(monkeypatch, *_cell_model("trinitymini-train-share16"),
                        limit)
    compiled = cfg["memory"]["5"]["step_bytes"]
    assert "(2,1,1,1,1)" in cfg["memory"]["5"]["remat_plan"]
    assert plan.rungs == (2, 1, 1, 1, 1)
    assert compiled <= plan.estimate_bytes <= compiled + 3 * 10**9
    assert compiled < int(limit * 15 / 16)
    # the gate's projection is kept with the out projection's output
    kept = remat_plan.residual_bytes(2, 8191, 2048, 32, 6144, 2,
                                     attn_width=4096, own_gate=True)
    assert plan.kept_bytes == 5 * kept[0] + kept[1]


@pytest.fixture(scope="module")
def v5e_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_rehearsed_trinity_step_compiles_for_a_v5e_under_its_plan(
        monkeypatch, v5e_chip):
    """The rehearsal's step, compiled for a described v5e under a plan that
    keeps every residual: Mosaic takes the windowed kernels (a band two
    tiles narrower than the row) beside the full ones, and the compiled
    step holds at least what the plan reckons (at this size every small
    tensor is padded to whole tiles, so the compiler's total stands far over
    the estimate: the cell's own size is the case above)."""
    import sys
    bench = os.path.join(REPO, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import run as harness
    from runners import train
    monkeypatch.setattr(attention, "_use_interpret", lambda: False)
    monkeypatch.setattr(remat_plan, "device_bytes_limit", lambda: 30_000_000)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
        cell, cfg = harness.resolve(manifest, "trinitymini-train-share16",
                                    True)
        made = train.make_plan(cell, cfg)

        def placed(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=v5e_chip), tree)

        state = placed(jax.eval_shape(made.build, jax.random.PRNGKey(0)))
        batch = placed({"tokens": jax.ShapeDtypeStruct(
            (cell["batch_per_chip"], cell["row_tokens"]), jnp.int32)})
        before = len(compile_cache.remat_plans())
        compiled = make_lm_train_step(SingleDevice()).lower(
            state, batch).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    plan, = compile_cache.remat_plans()[before:]
    assert plan.rungs == (3, 2, 2, 2, 2)
    text = compiled.as_text()
    for name in ("flash_swa_fwd", "flash_swa_bwd_dq", "flash_swa_bwd_dkv",
                 "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert re.search(rf"%{name}[.\d]* = .*tpu_custom_call", text), name
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total >= plan.estimate_bytes + plan.kept_bytes
