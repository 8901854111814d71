"""The reduction from trace events to numbers, on a hand-built event list
and on ``data/trace_events.*.json.gz``, one whole step cut from a real traced
window, and the readers that stand on it."""

import glob
import gzip
import json
import os
import re

import pytest

from _paths import BENCH
from lib import kernels, xplane

# (name, start_ns, duration_ns): two "steps" of 1000 ns on one device
EVENTS = [
    ("fusion.1", 0.0, 300.0),
    ("flash_fwd", 300.0, 200.0),          # back to back with fusion.1
    ("fusion.2", 450.0, 100.0),           # overlaps the kernel's tail
    ("flash_bwd", 700.0, 200.0),          # after a 150 ns gap
    ("fusion.1", 1000.0, 300.0),          # after a 100 ns gap
    ("flash_fwd", 1300.0, 200.0),
    ("copy.3", 1900.0, 300.0),            # after a 400 ns gap; runs past 2000
]


def test_busy_union_counts_overlap_once():
    assert xplane.busy_union_ns(EVENTS) == 550 + 200 + 500 + 300
    assert xplane.busy_union_ns([]) == 0
    nested = [("a", 0.0, 100.0), ("b", 10.0, 20.0), ("c", 50.0, 100.0)]
    assert xplane.busy_union_ns(nested) == 150


def test_clip_cuts_events_to_the_window():
    cut = xplane.clip(EVENTS, 100.0, 2000.0)
    assert cut[0] == ("fusion.1", 100.0, 200.0)
    assert cut[-1] == ("copy.3", 1900.0, 100.0)
    assert xplane.busy_union_ns(cut) == 450 + 200 + 500 + 100
    assert xplane.clip(EVENTS, 5000.0, 6000.0) == []


def test_time_by_name_and_matching():
    by = xplane.time_by_name(EVENTS)
    assert by["fusion.1"] == 600 and by["flash_fwd"] == 400
    assert xplane.time_matching(EVENTS, r"flash_(fwd|bwd)") == 600
    assert xplane.time_matching(EVENTS, r"^nothing$") == 0


def test_idle_gaps_longest_first_with_the_edges():
    gaps = xplane.idle_gaps(EVENTS, 0.0, 2000.0)
    assert gaps == [(1500.0, 400.0), (550.0, 150.0), (900.0, 100.0)]
    assert xplane.idle_gaps([], 0.0, 10.0) == [(0.0, 10.0)]
    late = xplane.idle_gaps([("a", 5.0, 2.0)], 0.0, 10.0)
    assert late == [(0.0, 5.0), (7.0, 3.0)]


def test_covering_span_is_the_innermost():
    spans = [("bench:window", 0.0, 2000.0), ("bench:settle", 1500.0, 300.0),
             ("bench:feed", 100.0, 50.0)]
    assert xplane.covering_span(spans, 1600.0) == "bench:settle"
    assert xplane.covering_span(spans, 900.0) == "bench:window"
    assert xplane.covering_span(spans, 5000.0).startswith("no benchmark span")


def _reader(name):
    import run as harness
    return harness.load_module("metrics", name).read


def _record(events, steps=2, t0=0.0, t1=2000.0):
    import run as harness
    cfg = harness.load_config(os.path.join(BENCH, "configs", "olmo-1b.json"))
    ops = {0: xplane.clip(events, t0, t1)}
    return {
        "config": cfg,
        "cell": {"batch_per_chip": 4, "row_tokens": 2048, "chips": 1},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 8_000_000_000},
        "memory_stats": {"peak_bytes_in_use": 8_000_000_000,
                         "peak_bytes_reserved": 4_000_000_000},
        "window": {"steps": 30, "seconds": 10.0, "start": 100.0,
                   "target_tokens_per_step": 8188},
        "spans": [("feed", 100.5, 0.001), ("dispatch", 100.6, 0.003),
                  ("feed", 99.0, 5.0), ("dispatch", 101.0, 0.005)],
        "trace": {"devices": ops, "steps": steps,
                  "window_s": (t1 - t0) / 1e9,
                  "busy_s": xplane.busy_union_ns(ops[0]) / 1e9},
    }


def test_readers_on_the_hand_built_trace(monkeypatch):
    from lib import flops, kernels, peaks
    monkeypatch.setattr(kernels, "FLASH_EVENT", r"flash_(fwd|bwd)")
    rec = _record(EVENTS)
    busy = 550 + 200 + 500 + 100
    assert _reader("device.idle_pct")(rec) == pytest.approx(
        100 * (1 - busy / 2000))
    assert _reader("flash.busy_share_pct")(rec) == pytest.approx(
        100 * 600 / busy)
    work = flops.flash_train_work(rec["config"], 4, 2047)
    least = work["flops"] / peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    assert _reader("flash_roofline")(rec) == pytest.approx(
        100 * least * 2 / 600e-9)
    assert _reader("device.peak_hbm_gb")(rec) == 12.0
    assert _reader("step.host_dispatch_ms")(rec) == pytest.approx(
        1e3 * (0.001 + 0.003 + 0.005) / 2)
    mfu = _reader("step_mfu")(rec)
    assert mfu == pytest.approx(100 * flops.lm_train_flops(
        rec["config"], 4, 2048) * 30 / 10.0 / 197e12)


def test_a_reader_that_finds_nothing_returns_nothing_never_zero(monkeypatch):
    from lib import kernels
    monkeypatch.setattr(kernels, "FLASH_EVENT", r"flash_(fwd|bwd)")
    rec = _record([e for e in EVENTS if not e[0].startswith("flash")])
    assert _reader("flash_roofline")(rec) is None
    assert _reader("flash.busy_share_pct")(rec) is None
    rec["trace"] = None
    for name in ("flash_roofline", "flash.busy_share_pct", "device.idle_pct"):
        assert _reader(name)(rec) is None
    rec["memory_stats"] = {}
    assert _reader("device.peak_hbm_gb")(rec) is None
    rec["device"]["platform"] = "cpu"
    assert _reader("step_mfu")(rec) is None


RECORDED = sorted(glob.glob(os.path.join(BENCH, "tests", "data",
                                         "trace_events.*.json.gz")))


@pytest.mark.parametrize("path", RECORDED or [None])
def test_reduction_on_a_recorded_trace(path):
    if path is None:
        pytest.skip("no recorded trace kept yet")
    with gzip.open(path, "rt") as f:
        kept = json.load(f)
    events = [tuple(e) for e in kept["events"]]
    t0, t1 = kept["window_ns"]
    cut = xplane.clip(events, t0, t1)
    busy = xplane.busy_union_ns(cut)
    assert busy == pytest.approx(kept["expect"]["busy_ns"])
    gaps = xplane.idle_gaps(events, t0, t1)
    assert busy + sum(g for _, g in gaps) == pytest.approx(t1 - t0)
    flash = xplane.time_matching(cut, kernels.FLASH_EVENT)
    assert flash == pytest.approx(kept["expect"]["flash_ns"])
    assert 0 < flash < busy
    assert sum(1 for e in events if re.search(kernels.FLASH_EVENT, e[0])) \
        == kept["expect"]["flash_events"]
    top = max(xplane.time_by_name(cut).items(), key=lambda kv: kv[1])
    assert top[0] == xplane.label(top[0])      # labels are already short


def test_label_merges_the_same_op_of_every_layer():
    a = ('%fusion.337 = (f32[2048]{0:T(1024)}, bf16[4,2047,2048]{2,1,0:T(8,128)'
         '(2,1)}) fusion(bf16[4,2047,2048]{2,1,0} %copy-done.66, f32[2048,8192]'
         '{1,0:T(8,128)} %state_params__block_0____mlp____wg____kernel___value'
         '.1), kind=kOutput, calls=%fused_computation.517')
    b = a.replace("fusion.337", "fusion.293").replace("block_0", "block_4")
    assert xplane.label(a) == xplane.label(b) == (
        "fusion (f32[2048], bf16[4,2047,2048]) "
        "<- block_N____mlp____wg____kernel")
    call = ('%attn.32 = (bf16[64,2047,128]{2,1,0}, f32[64,1,2047]{2,1,0}) '
            'custom-call(bf16[64,2047,128]{2,1,0} %bitcast.2142), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={bf16[64,2047,128]{2,1,0}}')
    assert xplane.label(call) == (
        "attn (bf16[64,2047,128], f32[64,1,2047]) tpu_custom_call")
    assert re.search(kernels.FLASH_EVENT, call)
    assert not re.search(kernels.FLASH_EVENT, a)
    assert xplane.label("jit_step(123)") == "jit_step(123)"
