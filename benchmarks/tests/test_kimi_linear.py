"""The Kimi-Linear family and its cell: the configuration's file against the
catalog's row, the leaves' draw, the FLOP and byte counts against counts by
hand, the two readers of the new operator on events as the chip names them,
and the cell's rehearsal through ``run.py``.  (The program against the
family's plain reference: ``tests/test_kimi_linear.py``.)"""

import json
import math
import os
import re
import subprocess
import sys

import pytest

from _paths import BENCH, ROOT
from lib import modules

CELL = "kimilinear-train-share32"
CONFIG = os.path.join(BENCH, "configs", "kimi-linear-48b-a3b.json")
REHEARSAL = os.path.join(BENCH, "configs", "rehearse-kimi-linear.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("kda.ms", "kda_roofline")


def _config(path=CONFIG):
    import run as harness
    return harness.load_config(path)


def _reader(name):
    import run as harness
    return harness.load_module("metrics", name).read


def test_configuration_holds_every_number_of_the_catalogs_row():
    """Every key of the catalog row's ``config`` under the same key and with
    the same value, but for the four the file lists as reduced, whose
    published values it states; the nested group is copied whole but for
    its two lists of layers; no width among the reduced."""
    cfg = _config()
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row, = [r for r in rows if r["source_url"] == cfg["source"]]
    assert row["name"] == "Kimi-Linear-48B-A3B-Instruct"
    assert sorted(cfg["reduced"]) == ["linear_attn_config", "num_experts",
                                      "num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    group, published = cfg["linear_attn_config"], \
        row["config"]["linear_attn_config"]
    assert {k: v for k, v in group.items() if not k.endswith("_layers")} \
        == {k: v for k, v in published.items() if not k.endswith("_layers")}
    # the cut: the model's first five layers, the leading dense one once and
    # four behind it, a whole period of the 3 : 1 pattern among them
    n = cfg["num_hidden_layers"]
    assert n == 5 and cfg["first_k_dense_replace"] == 1
    for name in ("kda_layers", "full_attn_layers"):
        assert group[name] == [i for i in published[name] if i <= n]
    assert modules.family_of(cfg)._layer_kinds(cfg) == (
        "kda", "kda", "kda", "mla", "kda")
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["router_num_experts"] == cfg["published"]["num_experts"]
    shares = cfg["published"]["num_experts"] // cfg["num_experts"]
    assert shares == 32 and "32 chips" in cfg["deployment"]
    assert cfg["first_expert_held"] % cfg["num_experts"] == 0
    assert cfg["first_expert_held"] + cfg["num_experts"] \
        <= cfg["router_num_experts"]
    for key in ("kda_gate_rank", "first_expert_held", "router_num_experts"):
        assert key in cfg["assumed"], key


def test_every_leaf_has_a_draw_and_the_parameters_are_the_cuts():
    from runners import train
    cfg = _config()
    family = modules.family_of(cfg)
    assert family.__file__ == os.path.join(BENCH, "families",
                                           "kimi_linear.py")
    cell = {"remat": True, "row_tokens": 64,
            "optimizer": {"name": "adamw", "lr": 3e-4}}
    shapes = train.make_plan(cell, cfg).shapes

    def count(prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p.startswith(prefix))

    kda = count("block_1/kda/")
    assert kda == (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096)
                   + 2304 * 32 + 4 * 3 * 4096 + 2 * 4096 + 32 + 128)
    assert count("block_3/attn/") == (2304 * 6144 + 2304 * 576 + 512
                                      + 512 * 8192 + 4096 * 2304)
    assert count("block_0/mlp/") == 3 * 2304 * 9216
    assert count("block_1/moe/") == (2304 * 256 + 3 * 2304 * 1024
                                     + 8 * 3 * 2304 * 1024)
    assert not count("block_0/moe/") and not count("block_1/mlp/")
    total = sum(math.prod(s) for s in shapes.values())
    assert total == cfg["parameters"]["as_run"] == 602_449_792
    for path, shape in shapes.items():
        mean, std = family.leaf_moments(path, shape)
        assert std > 0, path
        if path.endswith("scale"):
            assert mean == 1.0, path
    assert family.leaf_moments("block_0/kda/conv/kernel", (4, 12288)) \
        == (0.0, 0.5)
    assert family.leaf_moments("block_1/moe/experts/wo", (8, 1024, 2304)) \
        == (0.0, 1 / math.sqrt(1024))
    assert family.leaf_moments("block_3/attn/out/kernel", (32, 128, 2304)) \
        == (0.0, 1 / math.sqrt(4096))
    assert family.leaf_moments("block_0/kda/f_b/kernel", (128, 32, 128)) \
        == (0.0, 1 / math.sqrt(128))
    # g = -exp(A_log) softplus(z + dt_bias): the median decay a channel is
    # about 0.99 a token, two deviations of both draws stay in 0.89..0.9995
    a_log = family.leaf_moments("block_0/kda/A_log", (32,))
    dt = family.leaf_moments("block_0/kda/dt_bias", (32, 128))

    def decay(sigmas):
        z = dt[0] + sigmas * math.hypot(1.0, dt[1])
        return math.exp(-math.exp(a_log[0] + sigmas * a_log[1])
                        * math.log1p(math.exp(z)))

    assert 0.985 <= decay(0) <= 0.992
    assert 0.88 <= decay(2) < decay(-2) <= 0.9995


def test_train_flops_and_work_equal_counts_by_hand():
    cfg = _config(REHEARSAL)
    family = modules.family_of(cfg)
    rows, positions = 2, 149
    t = rows * positions
    kda = (2 * t * 32 * 4 * 16          # in_q, in_k, in_v, out: 2 heads of 8
           + 2 * t * 32 * 2             # in_b
           + 2 * 2 * t * 8 * (32 + 16)  # f_a, f_b and g_a, g_b at rank 8
           + 6 * t * 2 * 8 * 8)         # the recurrence
    mla = (2 * t * 32 * 4 * 12          # q: 4 heads of 8 + 4
           + 2 * t * 32 * 20            # kv_a: 16 + 4
           + 2 * t * 16 * 4 * 16        # kv_b: 4 heads of 8 + 8
           + 2 * rows * 4 * positions * positions * (12 + 8) / 2
           + 2 * t * 32 * 32)           # out
    experts = (2 * t * 32 * 16                      # the router, all 16
               + 6 * (t * 3 * 4 / 16) * 32 * 24     # 4 of 16 held, 3 a token
               + 6 * t * 32 * 24)                   # the shared one, no gate
    dense = 6 * t * 32 * 64
    head = 2 * t * 32 * 96
    by_hand = 3 * (4 * kda + mla + dense + 4 * experts + head)
    assert family.train_flops(cfg, rows, positions + 1) == \
        pytest.approx(by_hand, rel=1e-12)
    # at the cell's size: the issue's 17.8 TFLOP a step
    cell = _config()
    assert 17.3e12 < family.train_flops(cell, 2, 4096) < 18.3e12
    t = 2 * 4095
    rule = family.kda_rule_work(cell, 2, 4095)
    assert rule["flops"] == 4 * 3 * 6 * t * 32 * 128 * 128
    assert rule["bytes"] == 4 * 2 * t * 32 * (5 * 128 + 1) * 4
    assert 6.0e-3 < rule["bytes"] / 819e9 < 7.0e-3      # bytes bound it
    assert rule["flops"] / 197e12 < rule["bytes"] / 819e9
    flash = family.attention_work(cell, 2, 4095)
    assert flash["flops"] == 3 * 2 * 2 * 32 * 4095 * 4095 * (192 + 128) / 2
    assert flash["bytes"] == 2 * 32 * 4095 * 2 * 6 * (192 + 128)
    gmm = family.expert_matmul_work(cell, 2, 4095)
    assert gmm["flops"] == 4 * 9 * 2 * (t * 8 * 8 / 256) * 2304 * 1024


# ---------------------------------------------------------------------------
# the readers of the new operator
# ---------------------------------------------------------------------------

def _while(name, state):
    return (f"%{name} = (s32[]{{:T(128)}}, {state}{{3,2,1,0:T(8,128)S(1)}}, "
            "bf16[32,2,32,128,128]{4,3,2,1,0}) while((s32[], "
            f"{state}) %tuple.1), condition=%cond, body=%body")


def _call(name):
    return (f'%{name} = bf16[32,2,32,128,128] custom-call(%a), '
            'custom_call_target="tpu_custom_call"')


def _record(events, cfg=None):
    return {"config": cfg or _config(),
            "cell": {"batch_per_chip": 2, "row_tokens": 4096, "chips": 1},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "trace": {"devices": {0: events}, "steps": 2, "window_s": 1.0}}


def test_the_pattern_matches_the_rules_events_and_no_other():
    module = _reader("kda.ms").__globals__
    rx = re.compile(module["kda_event"](_config(), 2))
    assert rx.search(_while("while.48", "f32[2,32,128,128]"))
    assert rx.search(_call("kda_chunk_fwd.3"))
    assert rx.search(_call("kda_chunk_bwd"))
    assert not rx.search(_while("while.9", "f32[4,32,128,128]"))
    assert not rx.search(_while("while.9", "f32[2,16,128,128]"))
    assert not rx.search(_call("gdn_chunk_fwd.1"))
    assert not rx.search(_call("flash_fwd.1"))
    assert not rx.search("%kda_chunk_fwd.1 = bf16[8] fusion(%a), kind=kLoop")
    assert not rx.search("%fusion.7 = f32[2,32,128,128]{3,2,1,0} fusion("
                         "f32[2,32,128,128] %p), kind=kLoop")


def test_kernels_and_loops_are_read_as_their_union():
    """Two traced steps: a forward call, a loop that spans two events of
    its body (not counted twice), a backward call: 2 + 3 + 4 ms over two
    steps; the share of the roofline from the family's work."""
    ms = 1_000_000
    events = [(_call("kda_chunk_fwd.1"), 0, 2 * ms),
              (_while("while.3", "f32[2,32,128,128]"), 2 * ms, 3 * ms),
              ("%fusion.9 = bf16[2,32,128,128] fusion(%a)", 2 * ms, 1 * ms),
              ("%fusion.10 = bf16[2,32,128,128] fusion(%a)", 3 * ms, 2 * ms),
              (_call("kda_chunk_bwd.1"), 6 * ms, 4 * ms),
              (_call("flash_fwd.1"), 10 * ms, 5 * ms)]
    record = _record(events)
    assert _reader("kda.ms")(record) == pytest.approx(4.5)
    work = modules.family_of(record["config"]).kda_rule_work(
        record["config"], 2, 4095)
    assert _reader("kda_roofline")(record) == pytest.approx(
        100 * (work["bytes"] / 819e9) / 4.5e-3, rel=1e-9)


def test_nothing_to_read_gives_none_and_does_not_raise():
    """A program without the kernels (the parent of PR 33: loops of some
    state there may be, calls named ``kda_*`` there are not), a
    configuration without such layers, no trace: None, never an error."""
    loops_only = [(_while("while.3", "f32[2,32,128,128]"), 0, 5),
                  (_call("gdn_chunk_fwd.1"), 5, 5)]
    olmo = _config(os.path.join(BENCH, "configs", "olmo-1b.json"))
    qwen = _config(os.path.join(BENCH, "configs", "qwen3-next-80b-a3b.json"))
    both = [(_call("kda_chunk_fwd.1"), 0, 5)]
    for name in READERS:
        read = _reader(name)
        assert read(_record(loops_only)) is None
        assert read(dict(_record(both), trace=None)) is None
        assert read(dict(_record(both),
                         trace={"devices": {}, "steps": 0})) is None
        assert read(_record(both, olmo)) is None
        assert read(_record(both, qwen)) is None
        assert read(_record(both)) is not None


def test_manifest_lists_the_cell_for_its_two_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]}
    assert sorted(mine) == sorted(READERS)
    assert {m["layer"] for m in mine.values()} == {"linear attention"}
    assert all(m["moves"] == "train_tokens_per_s" and
               m["source"] == "device_trace" for m in mine.values())
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "train-b2s4096"
    config, = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert config["file"] == os.path.relpath(CONFIG, ROOT)
    # every metric without a list of its own is the new cell's to report
    assert sum("workloads" not in m for m in manifest["per_layer"]) == 13


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
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
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
