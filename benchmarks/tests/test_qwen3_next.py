"""The Qwen3-Next family and its cell: the configuration's file against the
catalog's row, the leaves' draw, the FLOP count against a count by hand, the
four readers of the new layers on a recorded step of the chip, and the cell's
rehearsal through ``run.py``.  (The program against the family's plain
reference: ``tests/test_qwen3_next.py``.)"""

import gzip
import json
import math
import os
import re
import subprocess
import sys

import pytest

from _paths import BENCH, ROOT
from lib import hybrid_names, modules, xplane

DATA = os.path.join(BENCH, "tests", "data")
CELL = "qwen3next-train-share16"
CONFIG = os.path.join(BENCH, "configs", "qwen3-next-80b-a3b.json")
RECORDED = os.path.join(DATA, f"trace_events.hybrid.{CELL}.json.gz")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("gdn.ms", "gdn_roofline", "moe_gmm.ms", "moe_gmm_roofline")


def _config(path=CONFIG):
    import run as harness
    return harness.load_config(path)


def _reader(name):
    import run as harness
    return harness.load_module("metrics", name).read


def test_configuration_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog row's ``config`` under the same key and with
    the same value, but for the three the file lists as reduced, whose
    published values it states; no width among them."""
    cfg = _config()
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row, = [r for r in rows if r["source_url"] == cfg["source"]]
    assert row["name"] == "Qwen3-Next-80B-A3B-Instruct"
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    # the cut's floors: a whole period, 8 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["router_num_experts"] == cfg["published"]["num_experts"]
    shares = cfg["published"]["num_experts"] // cfg["num_experts"]
    assert shares == 16 and "16 chips" in cfg["deployment"]
    assert cfg["first_expert_held"] % cfg["num_experts"] == 0
    assert cfg["first_expert_held"] + cfg["num_experts"] \
        <= cfg["router_num_experts"]


def test_every_leaf_has_a_draw_and_the_decay_lives_several_chunks():
    from runners import train
    cfg = dict(_config(), num_hidden_layers=4, vocab_size=1024)
    family = modules.family_of(cfg)
    assert family.__file__ == os.path.join(BENCH, "families", "qwen3_next.py")
    cell = {"remat": True, "row_tokens": 64,
            "optimizer": {"name": "adamw", "lr": 3e-4}}
    shapes = train.make_plan(cell, cfg).shapes
    assert sum(math.prod(s) for p, s in shapes.items()
               if p.startswith("block_")) == 547_873_856   # one period: the 547.9 M of PERF.md section 4
    for path, shape in shapes.items():
        mean, std = family.leaf_moments(path, shape)
        assert std > 0, path
        if path.endswith("scale"):
            assert mean == (1.0 if "/gdn/norm/" in path else 0.0), path
    assert family.leaf_moments("block_0/gdn/conv/kernel", (4, 8192)) \
        == (0.0, 0.5)
    assert family.leaf_moments("block_0/moe/experts/wo", (32, 512, 2048)) \
        == (0.0, 1 / math.sqrt(512))
    assert family.leaf_moments("block_3/attn/out/kernel", (16, 256, 2048)) \
        == (0.0, 1 / math.sqrt(4096))
    # g = -exp(A_log) softplus(a + dt_bias) at the medians of a and the draw
    for sigmas in (-1, 0, 1):
        a_log = family.leaf_moments("block_0/gdn/A_log", (32,))
        decay = math.exp(-math.exp(a_log[0] + sigmas * a_log[1])
                         * math.log1p(math.e))
        assert 0.98 <= decay <= 0.995, (sigmas, decay)


def test_train_flops_equal_a_count_by_hand_at_the_rehearsal_size():
    cfg = _config(os.path.join(BENCH, "configs", "rehearse-qwen3-next.json"))
    family = modules.family_of(cfg)
    rows, positions = 2, 149
    t = rows * positions
    linear = (2 * t * 32 * 96        # in_qkvz: 2 x (2 x 8 + 2 x 2 x 8) wide
              + 2 * t * 32 * 8       # in_ba: 2 key heads x (2 + 2)
              + 6 * t * 4 * 8 * 8    # the recurrence, 4 value heads
              + 2 * t * 32 * 32)     # out
    full = (2 * t * 32 * (4 * 32 + 2 * 2 * 16)      # q + gate, k, v
            + 2 * 2 * rows * 4 * positions * positions * 16 / 2
            + 2 * t * 64 * 32)                      # out
    experts = (2 * t * 32 * 16                      # the router, all 16
               + 6 * (t * 3 * 4 / 16) * 32 * 24     # 4 of 16 held, 3 a token
               + 6 * t * 32 * 24 + 2 * t * 32)      # the shared one, its gate
    head = 2 * t * 32 * 96
    by_hand = 3 * (3 * linear + full + 4 * experts + head)
    assert family.train_flops(cfg, rows, positions + 1) == \
        pytest.approx(by_hand, rel=1e-12)
    # at the cell's size: the issue's 1.30 GFLOP a target token
    cell = _config()
    per_token = family.train_flops(cell, 2, 4096) / (2 * 4095)
    assert 1.28e9 < per_token < 1.32e9


# ---------------------------------------------------------------------------
# the readers of the new layers
# ---------------------------------------------------------------------------

def _while(name, state):
    return (f"%{name} = (s32[]{{:T(128)}}, {state}{{3,2,1,0:T(8,128)S(1)}}, "
            "f32[64,2,32,64,128]{4,3,2,1,0:T(8,128)}, /*index=5*/bf16[64,2,"
            f"32,64,128]{{4,3,2,1,0}}) while((s32[], {state}) %tuple.1), "
            "condition=%cond, body=%body")


def test_patterns_match_their_events_and_no_other():
    cfg = _config()
    gdn = re.compile(hybrid_names.gdn_event(cfg, 2))
    assert gdn.search(_while("while.48", "f32[2,32,128,128]"))
    assert gdn.search(_while("while", "f32[2,32,128,128]"))
    assert gdn.search('%gdn_fwd.3 = bf16[8] custom-call(%p), '
                      'custom_call_target="tpu_custom_call"')
    # another state's loop, another batch, a fusion that reads the state
    assert not gdn.search(_while("while.9", "f32[8702,3072]"))
    assert not gdn.search(_while("while.9", "f32[4,32,128,128]"))
    assert not gdn.search("%fusion.7 = f32[2,32,128,128]{3,2,1,0} fusion("
                          "f32[2,32,128,128] %p), kind=kLoop")
    assert not gdn.search("%gdn_fwd.3 = bf16[8] fusion(%p), kind=kLoop")
    gmm = re.compile(hybrid_names.MOE_GMM_EVENT)
    for name in ("moe_gmm", "moe_gmm.17", "moe_tgmm.3"):
        assert gmm.search(f'%{name} = bf16[10240,512] custom-call(%a), '
                          'custom_call_target="tpu_custom_call"'), name
    for name in ("moe_gmm_x.1", "xmoe_gmm.1", "flash_fwd.1"):
        assert not gmm.search(f'%{name} = bf16[8] custom-call(%a), '
                              'custom_call_target="tpu_custom_call"'), name
    assert not gmm.search("%moe_gmm.1 = bf16[8] fusion(%a), kind=kLoop")


def _recorded():
    with gzip.open(RECORDED, "rt") as f:
        kept = json.load(f)
    t0, t1 = kept["window_ns"]
    events = xplane.clip([tuple(e) for e in kept["events"]], t0, t1)
    cfg = _config()
    return kept, {
        "config": cfg,
        "cell": {"batch_per_chip": 2, "row_tokens": 4096, "chips": 1},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "trace": {"devices": {0: events}, "steps": 1,
                  "window_s": (t1 - t0) / 1e9}}


def test_recorded_step_gives_the_four_new_metrics():
    """One step of the cell as the chip recorded it: nine loops of the
    delta rule (three layers, forward, recomputed forward, backward), 48
    grouped matmuls (four layers, 3 + 3 + 6); the loops' bodies are events
    nested in them and are not counted twice; both shares under 100%."""
    kept, record = _recorded()
    events = record["trace"]["devices"][0]
    gdn = re.compile(hybrid_names.gdn_event(record["config"], 2))
    gmm = re.compile(hybrid_names.MOE_GMM_EVENT)
    assert sum(1 for e in events if gdn.search(e[0])) == kept["expect"]["loops"]
    assert sum(1 for e in events if gmm.search(e[0])) == kept["expect"]["gmm"]
    got = {name: _reader(name)(record) for name in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["gdn.ms"] == pytest.approx(kept["expect"]["gdn.ms"], rel=1e-6)
    assert got["moe_gmm.ms"] == pytest.approx(kept["expect"]["moe_gmm.ms"],
                                              rel=1e-6)
    loops = [e for e in events if gdn.search(e[0])]
    assert got["gdn.ms"] == pytest.approx(
        sum(d for _, _, d in loops) / 1e6, rel=1e-6)      # they do not overlap
    assert 0 < got["gdn_roofline"] < 100 and 0 < got["moe_gmm_roofline"] < 100
    family = modules.family_of(record["config"])
    work = family.delta_rule_work(record["config"], 2, 4095)
    assert got["gdn_roofline"] == pytest.approx(
        100 * max(work["flops"] / 197e12, work["bytes"] / 819e9)
        / (got["gdn.ms"] / 1e3), rel=1e-9)


def test_nothing_to_read_gives_none_and_does_not_raise():
    _, record = _recorded()
    olmo = _config(os.path.join(BENCH, "configs", "olmo-1b.json"))
    for name in READERS:
        read = _reader(name)
        assert read(dict(record, trace=None)) is None
        assert read(dict(record, trace={"devices": {}, "steps": 0})) is None
        # a configuration without linear layers or held experts, on the
        # same events: no loop of its state's shape, no such work
        assert read(dict(record, config=olmo)) is None or name == "moe_gmm.ms"
    flash_only = [e for e in record["trace"]["devices"][0]
                  if "flash" in e[0]]
    bare = dict(record, trace=dict(record["trace"],
                                   devices={0: flash_only}))
    assert all(_reader(name)(bare) is None for name in READERS)


def test_manifest_lists_the_cell_for_its_four_metrics_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]}
    assert sorted(mine) == sorted(READERS)
    assert {m["layer"] for m in mine.values()} == {"linear attention",
                                                   "experts"}
    assert all(m["moves"] == "train_tokens_per_s" for m in mine.values())
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["workloads"][-1]["chips"] == 1
    assert manifest["configs"][-1]["file"] == os.path.relpath(CONFIG, ROOT)


# ---------------------------------------------------------------------------
# the cell's rehearsal, as the driver would run it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_through_run_py(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 99), "--seconds", "0.5", "--trace",
         str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3 and line["rehearsal"] is True
    assert line["compared"]["nonfinite_losses"]["value"] == 0
    if trace:
        assert "step.host_dispatch_ms" in line["metrics"]
        assert not set(READERS) & set(line["metrics"])   # no device line
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
