"""The all-reduce readers (``lib/collectives.py``, ``grad_sync.ms``,
``grad_sync_roofline``): on hand-built events, and on a recorded step of
``olmo1b-train-ddp4``."""

import glob
import gzip
import json
import os

import pytest

from _paths import BENCH
from lib import collectives

DATA = os.path.join(BENCH, "tests", "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "trace_events.ddp4.*.json.gz")))

SYNC = ("%psum_invariant.610 = f32[2048,8192]{1,0:T(8,128)} all-reduce("
        "f32[2048,8192]{1,0:T(8,128)} %bitcast_convert_fusion.1), "
        "channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, "
        "to_apply=%region_146.150")
COMBINED = ("%all-reduce.8 = (f32[2048,8192]{1,0}, f32[2048,16,128]{2,0,1}) "
            "all-reduce(f32[2048,8192]{1,0} %bitcast_convert_fusion.16, "
            "f32[2048,16,128]{2,0,1} %fusion.1217), channel_id=1")
START = ("%all-reduce-start.3 = f32[64]{0} all-reduce-start(f32[64]{0} "
         "%fusion.9), channel_id=2")
DONE = ("%all-reduce-done.3 = f32[64]{0} all-reduce-done(f32[64]{0} "
        "%all-reduce-start.3)")
NOT_ONE = [
    "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %all-reduce.8), kind=kLoop",
    "%all-reduce-scatter.1 = f32[8]{0} reduce-scatter(f32[32]{0} %p)",
    "%my_all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p)",
    "%all-reduce-done.4 = f32[64]{0} all-reduce-done(f32[64]{0} "
    "%all-reduce-start.4)",                 # its start was never seen
]


def _reader(name):
    import run as harness
    return harness.load_module("metrics", name).read


def test_all_reduces_are_told_by_the_whole_instruction_name_and_opcode():
    events = [(SYNC, 0.0, 10.0), ("%fusion.1 = f32[8] fusion()", 10.0, 5.0),
              (COMBINED, 15.0, 20.0), (START, 40.0, 1.0),
              ("%fusion.2 = f32[8] fusion()", 41.0, 30.0), (DONE, 71.0, 2.0)]
    events += [(name, 100.0 + i, 1.0) for i, name in enumerate(NOT_ONE)]
    got = collectives.all_reduce_intervals(events)
    # an asynchronous pair is one interval, start's beginning to done's end
    assert [(s, d) for _, s, d in got] == [(0.0, 10.0), (15.0, 20.0),
                                           (40.0, 33.0)]


def test_readers_average_over_chips_and_leave_one_chip_cells_out():
    shapes = {"embed": (1000, 100), "block_0/mlp/wi/kernel": (100, 400)}
    record = {
        "shapes": shapes,
        "device": {"kind": "TPU v5 lite", "count": 4},
        "trace": {"steps": 2, "devices": {
            0: [(SYNC, 0.0, 3e6), (COMBINED, 2e6, 3e6)],      # union 5 ms
            1: [(SYNC, 0.0, 3e6)],
            2: [(SYNC, 0.0, 3e6)],
            3: [(SYNC, 0.0, 3e6), ("%fusion.1 = f32[8] fusion()", 4e6, 9e6)],
        }}}
    ms = _reader("grad_sync.ms")(record)
    assert ms == pytest.approx((5 + 3 + 3 + 3) / 4 / 2)
    grad_bytes = 4 * (1000 * 100 + 100 * 400)
    least_s = 2 * 3 / 4 * grad_bytes / (1600e9 / 8)
    assert collectives.ring_all_reduce_bytes(grad_bytes, 4) == \
        1.5 * grad_bytes
    assert collectives.ring_all_reduce_bytes(grad_bytes, 1) == 0.0
    assert _reader("grad_sync_roofline")(record) == pytest.approx(
        100 * least_s / (ms / 1e3))
    one_chip = dict(record, trace={"steps": 2, "devices": {
        0: [("%fusion.1 = f32[8] fusion()", 0.0, 9e6)]}})
    for name in ("grad_sync.ms", "grad_sync_roofline"):
        assert _reader(name)(one_chip) is None           # never 0
        assert _reader(name)(dict(record, trace=None)) is None


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_readers_on_a_recorded_step_of_the_ddp_cell(path):
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    devices = {int(n): [tuple(e) for e in ev] for n, ev in
               recorded["all_reduces_of_other_chips"].items()}
    devices[0] = [tuple(e) for e in recorded["events"]]
    expect = recorded["expect"]
    assert len(devices) == recorded["chips"] == 4
    for ev in devices.values():
        # the MLP leaves one by one, a bucket a block for the rest, the
        # embedding, the small rest, and the loss mask's scalar count
        assert len(collectives.all_reduce_intervals(ev)) == \
            expect["all_reduces"] == 27
    # on chip 0 the excerpt keeps every op: nothing else matches
    assert sum(1 for name, _, _ in devices[0] if "all-reduce" in name) == 27
    from runners import train
    import run as harness
    cfg = harness.load_config(os.path.join(BENCH, "configs", "olmo-1b.json"))
    cell = {"remat": True, "row_tokens": 2048,
            "optimizer": {"name": "adamw", "lr": 3e-4}}
    record = {"shapes": train.make_plan(cell, cfg).shapes,
              "device": {"kind": "TPU v5 lite", "count": 4},
              "trace": {"steps": 1, "devices": devices}}
    ms = _reader("grad_sync.ms")(record)
    assert ms == pytest.approx(sum(
        expect["all_reduce_ns"][str(n)] for n in devices) / 4 / 1e6)
    assert 44.7 < ms < 44.9
    # 639,928,320 f32 gradients, 3/2 of them sent by each chip, at 200 GB/s
    least_ms = 1.5 * 4 * 639928320 / 200e9 * 1e3
    share = _reader("grad_sync_roofline")(record)
    assert share == pytest.approx(100 * least_ms / ms) and 42 < share < 43
