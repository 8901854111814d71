"""The reader of the full-causal flash backward (``flash.bwd_ms``): its
pattern on instructions as the chip names them, its reading of a step that
ran the fused kernel and of one that ran the pair (a recorded chip trace of
the pair among them), nothing to read without a trace, and its manifest
entry."""

import json
import os
import re

import pytest

from _paths import ROOT

NAME = "flash.bwd_ms"


def _reader(name):
    import run as harness
    return harness.load_module("metrics", name)


def _call(name):
    return (f'%{name} = (bf16[64,2047,128]{{2,1,0}}) custom-call(bf16[64,2047,'
            f'128]{{2,1,0}} %bitcast.2142), custom_call_target='
            f'"tpu_custom_call", operand_layout_constraints={{}}')


def _record(events, steps=2):
    return {"trace": {"devices": {0: events}, "steps": steps,
                      "window_s": 1.0}}


@pytest.mark.parametrize("name", ["flash_bwd", "flash_bwd.7", "flash_bwd_dq",
                                  "flash_bwd_dq.12", "flash_bwd_dkv.3"])
def test_the_pattern_takes_the_full_causal_backward(name):
    from lib import kernels
    pattern = _reader(NAME).BWD_EVENT
    assert re.search(pattern, _call(name))
    # the whole flash time still reads it
    assert re.search(kernels.FLASH_EVENT, _call(name))


@pytest.mark.parametrize("name", [
    "flash_fwd.1", "flash_swa_bwd.2", "flash_swa_bwd_dq", "flash_swa_bwd_dkv.4",
    "flash_bwd_x.1", "xflash_bwd.1", "moe_gmm.3", "fusion.5"])
def test_the_pattern_passes_every_other_call_over(name):
    assert not re.search(_reader(NAME).BWD_EVENT, _call(name))


def test_the_name_alone_is_not_enough():
    assert not re.search(_reader(NAME).BWD_EVENT,
                         "%flash_bwd.7 = bf16[8] fusion(%p), "
                         "calls=%fused_computation")


def test_either_implementation_reads_its_backward_alone():
    ms = 1_000_000
    fused = [(_call("flash_fwd.1"), 0, 3 * ms),
             (_call("flash_bwd.1"), 3 * ms, 5 * ms),
             (_call("flash_swa_bwd.1"), 8 * ms, 7 * ms)]
    pair = [(_call("flash_fwd.1"), 0, 3 * ms),
            (_call("flash_bwd_dq.1"), 3 * ms, 4 * ms),
            (_call("flash_bwd_dkv.1"), 7 * ms, 4 * ms),
            (_call("flash_swa_bwd_dq.1"), 11 * ms, 7 * ms)]
    read = _reader(NAME).read
    assert read(_record(fused)) == pytest.approx(2.5)
    assert read(_record(pair)) == pytest.approx(4.0)


def test_the_recorded_pair_reads_as_its_two_readers_together():
    """On a chip trace of the pair (``olmo1b-train-b4s2048``, the pair's names)
    the reading is ``flash.bwd_dq_ms`` and ``flash.bwd_dkv_ms`` added."""
    import glob
    import gzip
    from lib import xplane
    path, = glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                   "trace_events.named.*.json.gz"))
    with gzip.open(path, "rt") as f:
        kept = json.load(f)
    t0, t1 = kept["window_ns"]
    events = xplane.clip([tuple(e) for e in kept["events"]], t0, t1)
    record = _record(events, steps=1)
    both = [_reader(n).read(record) for n in ("flash.bwd_dq_ms",
                                              "flash.bwd_dkv_ms")]
    assert all(v and v > 0 for v in both)
    assert _reader(NAME).read(record) == pytest.approx(sum(both), rel=1e-9)


@pytest.mark.parametrize("trace", [None, {"devices": {}, "steps": 0},
                                   {"devices": {0: []}, "steps": 3}])
def test_nothing_to_read_gives_none(trace):
    assert _reader(NAME).read({"trace": trace}) is None


def test_the_manifest_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "flash kernels",
                     "moves": "train_tokens_per_s"}
    assert manifest["per_layer"][-1] == entry
