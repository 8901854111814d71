"""The AFMoE (Trinity) family and the two cells of PR 35: the configuration's
file against the catalog's row, the leaves' draw, the FLOP, byte and pair
counts against counts by hand and by an explicit mask, the two readers of
the windowed kernels on events as the chip names them, the program's
trace-time count of tiles against the mask, the manifest's entries found by
name, and the cell's rehearsal through ``run.py``.  (The program against the
family's plain reference: ``tests/test_afmoe.py``.)"""

import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from _paths import BENCH, ROOT
from lib import modules

CELL = "trinitymini-train-share16"
ZIPF = "olmo1b-train-b4s2048-zipf"
CONFIG = os.path.join(BENCH, "configs", "trinity-mini.json")
REHEARSAL = os.path.join(BENCH, "configs", "rehearse-trinity-mini.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("swa.ms", "swa_roofline")
WINDOWED, FULL = "sliding_attention", "full_attention"


def _config(path=CONFIG):
    import run as harness
    return harness.load_config(path)


def _reader(name):
    import run as harness
    return harness.load_module("metrics", name).read


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog row's ``config`` under the same key and with
    the same value, but for the five the file lists as reduced, whose
    published values it states; no width among the reduced."""
    cfg = _config()
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row, = [r for r in rows if r["source_url"] == cfg["source"]]
    assert row["name"] == "Trinity-Mini"
    assert sorted(cfg["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "sliding_window", "route_scale"):
        assert key not in cfg["reduced"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["route_scale"]) == (2048, 32, 4, 128, 6144, 1024, 8, 2048,
                                    2.826)
    # the cut: model layers 1-5 counted from 0, the leading dense layers
    # once, then one whole period of the 3 : 1 pattern
    n = cfg["num_hidden_layers"]
    assert n == 5 and cfg["num_dense_layers"] == 1
    assert cfg["layer_types"] == cfg["published"]["layer_types"][1:1 + n] \
        == [WINDOWED, WINDOWED, FULL, WINDOWED, WINDOWED]
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["router_num_experts"] == cfg["published"]["num_experts"] == 128
    shares = cfg["published"]["num_experts"] // cfg["num_experts"]
    assert shares == 16 and "16 chips" in cfg["deployment"]
    assert cfg["first_expert_held"] % cfg["num_experts"] == 0
    assert cfg["first_expert_held"] + cfg["num_experts"] \
        <= cfg["router_num_experts"]
    for key in ("router_num_experts", "first_expert_held", "window_edge",
                "rotation", "attention_gate", "expert_bias",
                "load_balancing_loss", "initialisation", "optimizer"):
        assert key in cfg["assumed"], key
    assert cfg["departures"] and cfg["precision"] and cfg["memory"]


def test_every_leaf_has_a_draw_and_the_parameters_are_the_cuts():
    from runners import train
    cfg = _config()
    family = modules.family_of(cfg)
    assert family.__file__ == os.path.join(BENCH, "families", "afmoe.py")
    cell = {"remat": True, "row_tokens": 64,
            "optimizer": {"name": "adamw", "lr": 3e-4}}
    shapes = train.make_plan(cell, cfg).shapes

    def count(prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p.startswith(prefix))

    for i in range(5):
        assert count(f"block_{i}/attn/") == 27_263_232 == (
            3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128)
        assert count(f"block_{i}/ln_") == 4 * 2048
    assert count("block_0/mlp/") == 3 * 2048 * 6144
    assert count("block_1/moe/") == (2048 * 128 + 3 * 2048 * 1024
                                     + 8 * 3 * 2048 * 1024)
    assert not count("block_0/moe/") and not count("block_1/mlp/")
    assert count("block_0/") == 65_020_160
    assert count("block_3/") == 84_156_672
    assert count("embed") + count("head") == 102_498_304
    total = sum(math.prod(s) for s in shapes.values())
    assert total == cfg["parameters"]["as_run"] == 504_147_200
    for path, shape in shapes.items():
        mean, std = family.leaf_moments(path, shape)
        assert std > 0, path
        if path.endswith("scale"):
            assert mean == 1.0, path
    assert family.leaf_moments("block_1/moe/experts/wo", (8, 1024, 2048)) \
        == (0.0, 1 / math.sqrt(1024))
    assert family.leaf_moments("block_3/attn/out/kernel", (32, 128, 2048)) \
        == (0.0, 1 / math.sqrt(4096))
    assert family.leaf_moments("block_3/attn/gate_proj/kernel",
                               (2048, 32, 128)) == (0.0, 1 / math.sqrt(2048))
    kwargs = family.model_kwargs(cfg, True)
    assert kwargs["layer_windows"] == (2048, 2048, 0, 2048, 2048)
    assert kwargs["layer_rotates"] == (True, True, False, True, True)


def _mask_pairs(positions, window):
    rows, cols = np.arange(positions)[:, None], np.arange(positions)[None, :]
    seen = rows >= cols
    if window is not None:
        seen &= rows - window < cols
    return int(seen.sum())


def test_train_flops_and_work_equal_counts_by_hand_and_by_mask():
    cfg = _config(REHEARSAL)
    family = modules.family_of(cfg)
    rows, positions = 2, 149
    window = cfg["sliding_window"]
    assert window < positions
    # the band's pairs against an explicit mask, at three sizes
    for p, w in ((positions, window), (149, 149), (149, 1), (64, 500),
                 (300, 7)):
        assert family.band_pairs(p, w) == _mask_pairs(p, w), (p, w)
        assert np.array_equal(
            np.asarray(family.band_mask(np.arange(p), np.arange(p), w)),
            (np.arange(p)[:, None] >= np.arange(p)[None, :])
            & (np.arange(p)[:, None] - w < np.arange(p)[None, :]))
    assert family.band_pairs(positions, None) == _mask_pairs(positions, None)
    t = rows * positions
    projections = 2 * t * 32 * 8 * (3 * 4 + 2 * 2)   # q gate out; k v
    pairs = 4 * _mask_pairs(positions, window) + _mask_pairs(positions, None)
    attention = rows * 4 * 4 * pairs * 8
    experts = (2 * t * 32 * 16                      # the router, all 16
               + 6 * (t * 3 * 4 / 16) * 32 * 24     # 4 of 16 held, 3 a token
               + 6 * t * 32 * 24)                   # the shared one, no gate
    dense = 6 * t * 32 * 64
    head = 2 * t * 32 * 96
    by_hand = 3 * (5 * projections + attention + dense + 4 * experts + head)
    assert family.train_flops(cfg, rows, positions + 1) == \
        pytest.approx(by_hand, rel=1e-12)
    swa = family.swa_work(cfg, rows, positions)
    assert swa["flops"] == 3 * rows * 4 * 4 * 4 * _mask_pairs(
        positions, window) * 8
    assert swa["bytes"] == 4 * rows * positions * 8 * 2 * 6 * (4 + 2)
    # at the cell's size: the issue's arithmetic
    cell = _config()
    assert family.band_pairs(8191, 2048) == 14_679_040
    assert family.band_pairs(8191, None) == 33_550_336
    flash = family.attention_work(cell, 2, 8191)
    assert 9.0e12 < flash["flops"] < 9.1e12           # 9.07 TFLOP a step
    banded = family.swa_work(cell, 2, 8191)
    assert banded["flops"] == 3 * 2 * 32 * 4 * 4 * 14_679_040 * 128
    assert 5.7e12 < banded["flops"] < 5.8e12          # 4 x 1.44
    assert banded["bytes"] == 4 * 2 * 8191 * 128 * 2 * 6 * (32 + 4)
    assert banded["flops"] / 197e12 > banded["bytes"] / 819e9   # FLOPs bound
    assert 34.9e12 < family.train_flops(cell, 2, 8192) < 35.2e12


# ---------------------------------------------------------------------------
# the readers of the windowed kernels
# ---------------------------------------------------------------------------

def _call(name):
    return (f'%{name} = bf16[64,8191,128] custom-call(%a), '
            'custom_call_target="tpu_custom_call"')


def _record(events, cfg=None):
    return {"config": cfg or _config(),
            "cell": {"batch_per_chip": 2, "row_tokens": 8192, "chips": 1},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "trace": {"devices": {0: events}, "steps": 2, "window_s": 1.0}}


def test_the_pattern_matches_the_windowed_calls_and_no_other():
    from lib import kernels, program_names
    rx = re.compile(_reader("swa.ms").__globals__["SWA_EVENT"])
    for name in ("flash_swa_fwd.3", "flash_swa_bwd_dq", "flash_swa_bwd_dkv.12"):
        assert rx.search(_call(name)), name
        # all attention is still read as one lump, and the full-causal
        # calls' own readers pass the windowed ones over
        assert re.search(kernels.FLASH_EVENT, _call(name)), name
        for full in (program_names.FLASH_FWD_EVENT,
                     program_names.FLASH_BWD_DQ_EVENT,
                     program_names.FLASH_BWD_DKV_EVENT):
            assert not re.search(full, _call(name)), name
    for name in ("flash_fwd.1", "flash_bwd_dq.2", "flash_bwd_dkv", "moe_gmm.4"):
        assert not rx.search(_call(name)), name
    assert not rx.search("%flash_swa_fwd.1 = bf16[8] fusion(%a), kind=kLoop")


def test_windowed_calls_are_read_and_the_roofline_is_the_bands():
    ms = 1_000_000
    events = [(_call("flash_swa_fwd.1"), 0, 2 * ms),
              (_call("flash_fwd.1"), 2 * ms, 5 * ms),
              (_call("flash_swa_bwd_dq.1"), 7 * ms, 3 * ms),
              (_call("flash_swa_bwd_dkv.1"), 10 * ms, 4 * ms),
              (_call("flash_bwd_dkv.1"), 14 * ms, 5 * ms)]
    record = _record(events)
    assert _reader("swa.ms")(record) == pytest.approx(4.5)
    work = modules.family_of(record["config"]).swa_work(
        record["config"], 2, 8191)
    assert _reader("swa_roofline")(record) == pytest.approx(
        100 * (work["flops"] / 197e12) / 4.5e-3, rel=1e-9)
    # and the accepted lump reads all five calls against all layers' work
    whole = modules.family_of(record["config"]).attention_work(
        record["config"], 2, 8191)
    assert _reader("flash_roofline")(record) == pytest.approx(
        100 * (whole["flops"] / 197e12) * 2 / 19e-3, rel=1e-9)


def test_nothing_to_read_gives_none_and_does_not_raise():
    """A program without the windowed kernels (the parent of PR 35), a
    family without ``swa_work``, no trace: None, never an error."""
    full_only = [(_call("flash_fwd.1"), 0, 5), (_call("flash_bwd_dq.1"), 5, 5)]
    both = full_only + [(_call("flash_swa_fwd.1"), 10, 5)]
    olmo = _config(os.path.join(BENCH, "configs", "olmo-1b.json"))
    for name in READERS:
        read = _reader(name)
        assert read(_record(full_only)) is None
        assert read(dict(_record(both), trace=None)) is None
        assert read(dict(_record(both),
                         trace={"devices": {}, "steps": 0})) is None
        assert read(_record(both)) is not None
    assert _reader("swa_roofline")(_record(both, olmo)) is None


def test_manifest_lists_both_cells_and_the_two_metrics_by_name():
    manifest = _manifest()
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["name"] in READERS}
    assert sorted(mine) == sorted(READERS)
    for metric in mine.values():
        assert metric["workloads"] == [CELL]
        assert (metric["layer"], metric["moves"], metric["source"]) == (
            "flash kernels", "train_tokens_per_s", "device_trace")
    assert mine["swa_roofline"]["unit"] == "%"
    # no other metric names the new cells: what they report beside these is
    # every metric without a list of its own
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"] not in READERS
                and {CELL, ZIPF} & set(m.get("workloads", ()))]
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"],
            cells[CELL]["chips"]) == ("trinity-mini", "train-b2s8192", 1)
    assert (cells[ZIPF]["config"], cells[ZIPF]["traffic"],
            cells[ZIPF]["chips"]) == ("olmo-1b", "train-b4s2048-zipf", 1)
    config, = [c for c in manifest["configs"] if c["name"] == "trinity-mini"]
    assert config["file"] == os.path.relpath(CONFIG, ROOT)
    assert config["source"] == _config()["source"]
    assert sorted(config["reduced"]) == sorted(_config()["reduced"])
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_new_cells_files_are_what_the_issue_names():
    import run as harness
    manifest = _manifest()
    cell, cfg = harness.resolve(manifest, CELL, False)
    assert (cell["batch_per_chip"], cell["row_tokens"],
            cell["token_distribution"], cell["runner"], cell["remat"]) == (
        2, 8192, "uniform", "train", True)
    assert cell["optimizer"] == {"name": "adamw", "lr": 0.0003}
    assert cfg["model_type"] == "afmoe"
    zipf, olmo = harness.resolve(manifest, ZIPF, False)
    base, _ = harness.resolve(manifest, "olmo1b-train-b4s2048", False)
    assert zipf["token_distribution"] == "zipf"
    for key in ("batch_per_chip", "row_tokens", "runner", "remat",
                "optimizer", "vocab_chunk_size", "chips", "config"):
        assert zipf[key] == base[key], key
    # limits of its own readings, not the uniform cell's file copied
    assert zipf["limits_from"] != base["limits_from"]
    from lib import tokens
    ids = tokens.batch_tokens(2 ** 31 + 5, 0, 4, 2048, olmo["vocab_size"],
                              "zipf")
    assert ids.shape == (4, 2048) and ids.dtype == np.int32
    assert (ids == 0).mean() > 0.05 > (ids == 1000).mean()


def test_the_programs_count_of_tiles_agrees_with_a_count_from_the_mask():
    """What a windowed layer records at trace time (computed, needed and
    causal tiles, from shapes alone) against counts from an explicit mask
    at a small size with several blocks, and the cell's own shapes by
    arithmetic: fewer tiles computed than a causal call's."""
    from dtdl_tpu.ops.attention import band_tiles
    positions, window, block = 700, 200, 128
    tiles = band_tiles(positions, positions, 128, window, block, block)
    rows, cols = np.arange(positions)[:, None], np.arange(positions)[None, :]
    causal = rows >= cols
    band = causal & (rows - window < cols)

    def blocks(mask):
        return sum(bool(mask[i:i + block, j:j + block].any())
                   for i in range(0, positions, block)
                   for j in range(0, positions, block))

    assert tiles["computed_tiles"] == tiles["computed_tiles_dkv"] \
        == tiles["needed_tiles"] == blocks(band) == 15
    assert tiles["causal_tiles"] == blocks(causal) == 21
    assert tiles["band_pairs"] == int(band.sum()) \
        == modules.family_of(_config()).band_pairs(positions, window)
    cell = band_tiles(8191, 8191, 128, 2048)
    assert (cell["computed_tiles"], cell["needed_tiles"],
            cell["causal_tiles"]) == (21, 21, 36)
    assert cell["band_pairs"] / (1024 * 1024) == pytest.approx(14.0, abs=0.01)


# ---------------------------------------------------------------------------
# the cell's rehearsal, as the driver would run it
# ---------------------------------------------------------------------------

def test_cell_rehearses_through_run_py():
    """One traced rehearsal (the untraced line's shape is the harness's own
    and is checked on the accepted cells: this file's cases run in tier 1
    behind them on one worker)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 99), "--seconds", "0.5", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3 and line["rehearsal"] is True
    assert line["compared"]["nonfinite_losses"]["value"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "step.host_dispatch_ms" in line["metrics"]
    assert not set(READERS) & set(line["metrics"])       # no device line
