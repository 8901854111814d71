"""The yardstick's arithmetic: FLOPs against the program's original, the
roofline's bound, the token generator, the weights."""

import json
import os

import numpy as np
import pytest

from _paths import BENCH
from lib import flops, modules, peaks, tokens, weights


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["rehearse-tiny", "olmo-1b", "olmo-7b"])
def test_train_flops_equal_the_programs(name):
    from dtdl_tpu.obs import goodput
    from dtdl_tpu.models.transformer import TransformerLM
    cfg = _cfg(name)
    olmo = modules.load_file(os.path.join(BENCH, "families", "olmo.py"),
                             "families")
    model = TransformerLM(**olmo.model_kwargs(cfg, remat=True))
    for batch, row in ((1, 128), (4, 2048)):
        assert flops.lm_train_flops(cfg, batch, row) == pytest.approx(
            goodput.lm_train_flops(model, batch, row), rel=1e-12)


def test_tiny_equals_the_programs_preset():
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.obs import goodput
    tiny, cfg = transformer_lm("tiny"), _cfg("rehearse-tiny")
    assert flops.lm_train_flops(cfg, 2, 128) == pytest.approx(
        goodput.lm_train_flops(tiny, 2, 128), rel=1e-12)


def test_flash_work_is_the_causal_half_and_flops_bound_at_2048():
    cfg = _cfg("olmo-1b")
    work = flops.flash_train_work(cfg, 4, 2047)
    b, h, s, d, layers = 4, 16, 2047, 128, cfg["num_hidden_layers"]
    assert work["flops"] == layers * 3 * (4 * b * h * s * s * d / 2)
    assert work["bytes"] == layers * 12 * b * h * s * d * 2
    least, bound = flops.roofline_seconds(work, peaks.peaks_for("TPU v5 lite"))
    assert bound == "flops" and least == work["flops"] / 197e12
    short = flops.flash_train_work(cfg, 4, 64)
    assert flops.roofline_seconds(
        short, peaks.peaks_for("TPU v5 lite"))[1] == "bytes"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_batches_depend_on_seed_and_index_alone_and_rows_differ():
    big = 2 ** 31 + 12345
    a = tokens.batch_tokens(big, 3, 4, 2048, 50304)
    assert a.dtype == np.int32 and a.shape == (4, 2048)
    assert a.min() >= 0 and a.max() < 50304
    assert np.array_equal(a, tokens.batch_tokens(big, 3, 4, 2048, 50304))
    assert not np.array_equal(a, tokens.batch_tokens(big, 4, 4, 2048, 50304))
    assert not np.array_equal(a, tokens.batch_tokens(big + 1, 3, 4, 2048,
                                                     50304))
    assert len({row.tobytes() for row in a}) == 4
    z = tokens.batch_tokens(1, 0, 2, 512, 1000, "zipf")
    assert (z < 10).mean() > 0.2


def test_weights_depend_on_seed_and_path_alone():
    import jax
    key = weights.seed_key(2 ** 31 + 7)
    moments = weights.leaf_moments
    a = weights.make_leaf(key, "block_0/attn/q/kernel", (8, 2, 4), moments)
    again = weights.make_params(key, {"x/kernel": (3, 3),
                                      "block_0/attn/q/kernel": (8, 2, 4)},
                                moments)
    assert np.array_equal(a, again["block_0/attn/q/kernel"])
    other = weights.make_leaf(weights.seed_key(7), "block_0/attn/q/kernel",
                              (8, 2, 4), moments)
    assert not np.array_equal(a, other)
    scale = weights.make_leaf(key, "ln_f/scale", (4096,), moments)
    assert abs(float(scale.mean()) - 1.0) < 0.02
    assert jax.numpy.isfinite(a).all()
