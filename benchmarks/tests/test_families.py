"""A configuration's family (``lib/modules.py``): ``families/olmo.py`` gives
exactly what the yardstick's dense arithmetic gives, a family that is not
there is an error that names its file, and a second family's own arithmetic
is what the shared readers read."""

import json
import os

import numpy as np
import pytest

from _paths import BENCH
from lib import flops, modules, reference, tokens, weights

DATA = os.path.join(BENCH, "tests", "data")
NAMES = ("model_kwargs", "leaf_moments", "loss_and_grads", "train_flops",
         "attention_work")


def _config(path):
    import run as harness
    return harness.load_config(path)


@pytest.mark.parametrize("name", ["olmo-1b", "olmo-7b"])
def test_olmo_family_equals_the_direct_calls_exactly(name):
    """Every width as in the configuration's file; one block and 1,024 rows
    of the vocabulary, so that a float32 step runs on the CPU in seconds."""
    import jax
    from runners import train
    cfg = _config(os.path.join(BENCH, "configs", name + ".json"))
    assert cfg["family"] == os.path.join(BENCH, "families", "olmo.py")
    family = modules.family_of(cfg)
    assert sorted(n for n in vars(family) if not n.startswith("_")
                  and callable(getattr(family, n))) == sorted(NAMES)

    cfg = dict(cfg, num_hidden_layers=1, vocab_size=1024)
    cell = {"remat": True, "row_tokens": 16,
            "optimizer": {"name": "adamw", "lr": 3e-4}}
    shapes = train.make_plan(cell, cfg).shapes
    key = weights.seed_key(2 ** 31 + 27)
    through = jax.jit(lambda k: weights.make_params(
        k, shapes, family.leaf_moments))(key)
    direct = jax.jit(lambda k: weights.make_params(
        k, shapes, weights.leaf_moments))(key)
    assert set(through) == set(direct) == set(shapes)
    for path in shapes:
        assert np.array_equal(through[path], direct[path]), path

    batch = tokens.batch_tokens(27, 0, 2, 16, cfg["vocab_size"])
    loss_a, grads_a = jax.jit(
        lambda p, t: family.loss_and_grads(p, t, cfg, "f32"))(direct, batch)
    loss_b, grads_b = jax.jit(
        lambda p, t: reference.loss_and_grads(p, t, cfg, "f32"))(direct, batch)
    assert float(loss_a) == float(loss_b)
    for path in shapes:
        assert np.array_equal(grads_a[path], grads_b[path]), path

    for rows, row in ((1, 128), (4, 2048), (16, 2048)):
        assert family.train_flops(cfg, rows, row) == flops.lm_train_flops(
            cfg, rows, row)
        assert family.attention_work(cfg, rows, row - 1) == \
            flops.flash_train_work(cfg, rows, row - 1)


def test_unknown_model_type_exits_naming_the_family_file(tmp_path):
    from runners import train
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "novel.json"
    path.write_text(json.dumps({"model_type": "no-such-family",
                                "hidden_size": 64}))
    cfg = _config(str(path))
    missing = os.path.join(str(tmp_path), "families", "no-such-family.py")
    assert cfg["family"] == missing
    cell = {"remat": True, "row_tokens": 16,
            "optimizer": {"name": "adamw", "lr": 3e-4}}
    for call in (lambda: modules.family_of(cfg),
                 lambda: train.make_plan(cell, cfg)):
        with pytest.raises(SystemExit, match="families/no-such-family.py"):
            call()


def test_shared_readers_read_the_second_familys_own_arithmetic():
    cfg = _config(os.path.join(DATA, "configs", "toy.json"))
    toy = modules.family_of(cfg)
    assert toy.__file__ == os.path.join(DATA, "families", "toy.py")
    assert toy is modules.family_of(dict(cfg))          # loaded once
    assert toy.leaf_moments("block_0/mlp/wi/kernel", (64, 128)) != \
        weights.leaf_moments("block_0/mlp/wi/kernel", (64, 128))
    import run as harness
    record = {
        "config": cfg, "cell": {"batch_per_chip": 2, "chips": 4,
                                "row_tokens": 128},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
        "window": {"steps": 10, "seconds": 2.0}}
    mfu = harness.load_module("metrics", "step_mfu").read(record)
    assert mfu == pytest.approx(
        100 * toy.train_flops(cfg, 8, 128) * 10 / 2.0 / (4 * 197e12))
    # the dense count would credit the attention the toy leaves out
    hf = {"hidden_size": 64, "num_attention_heads": 4,
          "intermediate_size": 128, "num_hidden_layers": 2,
          "vocab_size": 256}
    assert toy.train_flops(cfg, 8, 128) < flops.lm_train_flops(hf, 8, 128)
