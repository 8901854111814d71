"""The JoyAI-LLM-Flash family and its cell: the configuration's file
against the catalog's row, the leaves' draw and the parameters of the cut,
the FLOP counts against counts by hand, the reader of the
multi-token-prediction module on events as the chip names them, and the
manifest's entries found by name.
(The program against the family's plain reference, the module's anchor in
a compiled step and the cell's rehearsal through ``run.py``:
``tests/test_joyai_llm_flash.py``.)"""

import json
import math
import os

import pytest

from _paths import BENCH, ROOT
from lib import modules
from test_kimi_linear import CATALOG    # the catalog of public architectures

CELL = "joyaiflash-train-share32"
CONFIG = os.path.join(BENCH, "configs", "joyai-llm-flash.json")
REHEARSAL = os.path.join(BENCH, "configs", "rehearse-joyai-llm-flash.json")
READERS = ("mtp.ms",)


def _config(path=CONFIG):
    import run as harness
    return harness.load_config(path)


def _reader(name):
    import run as harness
    return harness.load_module("metrics", name)


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog row's ``config`` under the same key and with
    the same value, but for the three the file lists as reduced, whose
    published values it states; no width among the reduced."""
    cfg = _config()
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row, = [r for r in rows if r["source_url"] == cfg["source"]]
    assert row["name"] == "JoyAI-LLM-Flash"
    assert sorted(cfg["reduced"]) == ["n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    # the cut: the leading dense layer once and four expert layers behind
    # it, the module, 8 of 256 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["num_nextn_predict_layers"] == 1
    assert cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["router_num_experts"] == cfg["published"]["n_routed_experts"]
    shares = cfg["published"]["n_routed_experts"] // cfg["n_routed_experts"]
    assert shares == 32 and "32 chips" in cfg["deployment"]
    assert cfg["first_expert_held"] % cfg["n_routed_experts"] == 0
    assert cfg["first_expert_held"] + cfg["n_routed_experts"] \
        <= cfg["router_num_experts"]
    for key in ("router_num_experts", "first_expert_held", "mtp_layout",
                "mtp_loss_weight", "e_score_correction_bias",
                "initialisation"):
        assert key in cfg["assumed"], key
    assert cfg["mtp_loss_weight"] == 0.3


def test_every_leaf_has_a_draw_and_the_parameters_are_the_cuts():
    from runners import train
    cfg = _config()
    family = modules.family_of(cfg)
    assert family.__file__ == os.path.join(BENCH, "families",
                                           "joyai_llm_flash.py")
    cell = {"remat": True, "row_tokens": 64,
            "optimizer": {"name": "adamw", "lr": 3e-4}}
    shapes = train.make_plan(cell, cfg).shapes

    def count(prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p.startswith(prefix))

    latent = (2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512
              + 512 * 32 * 256 + 32 * 128 * 2048)
    assert latent == cfg["parameters"]["latent_attention_a_layer"]
    for i in range(5):
        assert count(f"block_{i}/attn/") == latent
    assert count("block_0/mlp/") == 3 * 2048 * 7168
    experts = 2048 * 256 + 3 * 2048 * 768 + 8 * 3 * 2048 * 768
    assert count("block_1/moe/") == experts
    assert not count("block_0/moe/") and not count("block_1/mlp/")
    assert count("block_0/") == cfg["parameters"]["dense_layer_1"]
    assert count("block_3/") == cfg["parameters"]["expert_layer"] \
        == latent + experts + 2 * 2048
    assert count("mtp/") == cfg["parameters"]["mtp_module"] \
        == 3 * 2048 + 4096 * 2048 + count("block_4/")
    assert count("mtp/block/") == count("block_4/")
    assert shapes["mtp/eh_proj/kernel"] == (4096, 2048)
    assert count("embed") + count("head") \
        == cfg["parameters"]["embedding_and_head"] == 2 * 16160 * 2048
    total = sum(math.prod(s) for s in shapes.values())
    assert total == cfg["parameters"]["as_run"] == 491_696_128
    for path, shape in shapes.items():
        mean, std = family.leaf_moments(path, shape)
        assert std > 0, path
        if path.endswith("scale"):
            assert mean == 1.0, path
    assert family.leaf_moments("mtp/eh_proj/kernel", (4096, 2048)) \
        == (0.0, 1 / 64)
    assert family.leaf_moments("block_1/attn/q_b/kernel", (1536, 32, 192)) \
        == (0.0, 1 / math.sqrt(1536))
    assert family.leaf_moments("block_1/attn/out/kernel", (32, 128, 2048)) \
        == (0.0, 1 / 64)
    assert family.leaf_moments("block_1/moe/experts/wo", (8, 768, 2048)) \
        == (0.0, 1 / math.sqrt(768))


def test_train_flops_and_work_equal_counts_by_hand():
    cfg = _config(REHEARSAL)
    family = modules.family_of(cfg)
    rows, positions = 2, 149

    def latent(p):
        t = rows * p
        return (2 * t * 32 * 12                    # q_a
                + 2 * t * 12 * 4 * 12              # q_b: 4 heads of 8 + 4
                + 2 * t * 32 * 20                  # kv_a: 16 + 4
                + 2 * t * 16 * 4 * 16              # kv_b: 4 heads of 8 + 8
                + 2 * rows * 4 * p * p * (12 + 8) / 2
                + 2 * t * 32 * 32)                 # out

    def experts(t):
        return (2 * t * 32 * 16                    # the router, all 16
                + 6 * (t * 3 * 4 / 16) * 32 * 24   # 4 of 16 held, 3 a token
                + 6 * t * 32 * 24)                 # the shared one, no gate

    t, ahead = rows * positions, rows * (positions - 1)
    model = (5 * latent(positions) + 6 * t * 32 * 64 + 4 * experts(t)
             + 2 * t * 32 * 96)
    module = (2 * ahead * 64 * 32 + latent(positions - 1) + experts(ahead)
              + 2 * ahead * 32 * 96)
    assert family.train_flops(cfg, rows, positions + 1) == \
        pytest.approx(3 * (model + module), rel=1e-12)
    # at the cell's size: about 870 M forward FLOPs a token, the module's
    # (eh_proj, its block, its head) about a fifth
    cell = _config()
    per_token = family.train_flops(cell, 2, 4096) / 3 / (2 * 4095)
    assert 850e6 < per_token < 890e6
    flash = family.attention_work(cell, 2, 4095)
    assert flash["flops"] == 3 * 2 * 2 * 32 * (
        5 * 4095 ** 2 + 4094 ** 2) * (192 + 128) / 2
    assert flash["bytes"] == 2 * 32 * (5 * 4095 + 4094) * 2 * 6 * (192 + 128)
    gmm = family.expert_matmul_work(cell, 2, 4095)
    assert gmm["flops"] == 9 * 2 * 2 * (4 * 4095 + 4094) * 8 * 8 / 256 \
        * 2048 * 768


# ---------------------------------------------------------------------------
# the reader of the module
# ---------------------------------------------------------------------------

def _event(result, op="fusion", operands="bf16[2,4095,2048] %p"):
    return f"%{op}.7 = {result} {op}({operands}), kind=kLoop"


def _record(events, cfg=None):
    return {"config": cfg or _config(),
            "cell": {"batch_per_chip": 2, "row_tokens": 4096, "chips": 1},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "trace": {"devices": {0: events}, "steps": 2, "window_s": 1.0}}


def test_the_anchor_matches_the_modules_results_and_no_other():
    reader = _reader("mtp.ms")
    dims = reader.mtp_dims(_config(), {"batch_per_chip": 2,
                                       "row_tokens": 4096})
    assert dims == {4094, 8188, 8188 * 8}
    mine = [_event("bf16[2,4094,2048]{2,1,0:T(8,128)(2,1)}"),
            _event("(f32[8188,256]{1,0}, s32[8188,8]{1,0})"),
            _event("s32[65504]{0}", op="sort"),
            _event("f32[2,4094]{1,0}", op="reduce")]
    theirs = [_event("bf16[2,4095,2048]{2,1,0}",
                     operands="bf16[2,4094,2048] %p"),
              _event("bf16[8190,2048]{1,0}"), _event("s32[65520]{0}"),
              _event("f32[40940]{0}"), _event("bf16[4096,2048]{1,0}"),
              '%flash_fwd.3 = bf16[2,32,4096,128]{3,2,1,0} custom-call('
              'bf16[2,32,4094,192] %q), custom_call_target="tpu_custom_call"']
    assert all(reader.is_mtp_event(e, dims) for e in mine)
    assert not any(reader.is_mtp_event(e, dims) for e in theirs)


def test_the_modules_events_are_read_as_their_union():
    """Two traced steps: two events of the module, one inside the other
    (not counted twice), and events of the main path beside them."""
    ms = 1_000_000
    events = [(_event("bf16[2,4094,4096]{2,1,0}"), 0, 3 * ms),
              (_event("bf16[2,4094,2048]{2,1,0}"), 1 * ms, 1 * ms),
              (_event("bf16[2,4095,2048]{2,1,0}"), 3 * ms, 5 * ms),
              (_event("f32[8188,256]{1,0}"), 8 * ms, 2 * ms)]
    assert _reader("mtp.ms").read(_record(events)) == pytest.approx(2.5)


def test_nothing_to_read_gives_none_and_does_not_raise():
    """A configuration without the module, no trace, a trace without the
    module's events (the parent's program cannot run the cell at all):
    None, never an error."""
    read = _reader("mtp.ms").read
    kimi = _config(os.path.join(BENCH, "configs", "kimi-linear-48b-a3b.json"))
    olmo = _config(os.path.join(BENCH, "configs", "olmo-1b.json"))
    theirs = [(_event("bf16[2,4095,2048]{2,1,0}"), 0, 5)]
    mine = [(_event("bf16[2,4094,2048]{2,1,0}"), 0, 5)]
    assert read(_record(theirs)) is None
    assert read(dict(_record(mine), trace=None)) is None
    assert read(dict(_record(mine), trace={"devices": {}, "steps": 0})) \
        is None
    assert read(_record(mine, kimi)) is None
    assert read(_record(mine, olmo)) is None
    assert read(_record(mine)) is not None


# ---------------------------------------------------------------------------
# the manifest and the cells' files
# ---------------------------------------------------------------------------

def test_manifest_lists_the_cell_and_the_metric_by_name():
    manifest = _manifest()
    metric, = [m for m in manifest["per_layer"] if m["name"] in READERS]
    assert metric == {"name": "mtp.ms", "unit": "ms", "better": "lower",
                      "source": "device_trace",
                      "layer": "multi-token prediction",
                      "moves": "train_tokens_per_s", "workloads": [CELL]}
    # beside it the grouped-matmul pair of the other expert cell (the same
    # kernels, and the family's expert_matmul_work) lists the new cell; what
    # it reports beside these is every metric without a list of its own
    assert [m["name"] for m in manifest["per_layer"]
            if m["name"] not in READERS
            and CELL in m.get("workloads", ())] == [
        "moe_gmm.ms", "moe_gmm_roofline"]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai-llm-flash", "train-b2s4096", 1)
    config, = [c for c in manifest["configs"]
               if c["name"] == "joyai-llm-flash"]
    assert config["file"] == os.path.relpath(CONFIG, ROOT)
    assert config["source"] == _config()["source"]
    assert sorted(config["reduced"]) == sorted(_config()["reduced"])


def test_the_cells_file_holds_the_settings_it_states():
    import run as harness
    cell, cfg = harness.resolve(_manifest(), CELL, False)
    assert (cell["batch_per_chip"], cell["row_tokens"],
            cell["token_distribution"], cell["runner"], cell["remat"],
            cell["vocab_chunk_size"], cell["trace_seconds"]) == (
        2, 4096, "uniform", "train", True, 0, 3.0)
    assert cell["optimizer"] == {"name": "adamw", "lr": 0.0003}
    assert cfg["model_type"] == "joyai_llm_flash"
    assert "x1" in cell["limits_from"]["where"]
