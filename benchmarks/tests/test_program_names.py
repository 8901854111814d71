"""The readers of what the program names itself (``lib/program_names.py``):
the three flash kernels by their ``pallas_call`` names, on a recorded step
with the new names and on the old one without; the set-up readers on a
hand-built compile account and through ``run.py --rehearse --trace 1``; the
manifest's entries for both; and ``tools/scope_dump.py``'s reductions."""

import glob
import gzip
import json
import os
import re
import subprocess
import sys
from collections import namedtuple

import pytest

from _paths import BENCH, ROOT
from lib import kernels, program_names, xplane

DATA = os.path.join(BENCH, "tests", "data")
FLASH_READERS = ("flash.fwd_ms", "flash.bwd_dq_ms", "flash.bwd_dkv_ms")
SETUP_READERS = ("setup.trace_lower_s", "setup.compile_load_s",
                 "setup.other_programs_s", "setup.cache_hit_pct")
NAMED = sorted(glob.glob(os.path.join(DATA, "trace_events.named.*.json.gz")))
OLD = os.path.join(DATA, "trace_events.olmo1b-train-b4s2048.json.gz")


def _reader(name):
    import run as harness
    return harness.load_module("metrics", name).read


def _recorded(path):
    with gzip.open(path, "rt") as f:
        kept = json.load(f)
    t0, t1 = kept["window_ns"]
    events = xplane.clip([tuple(e) for e in kept["events"]], t0, t1)
    return kept, {"trace": {"devices": {0: events}, "steps": 1,
                            "busy_s": xplane.busy_union_ns(events) / 1e9,
                            "window_s": (t1 - t0) / 1e9}}


# ---------------------------------------------------------------------------
# the flash kernels by name
# ---------------------------------------------------------------------------

def _call(name):
    return (f'%{name} = (bf16[64,2047,128]{{2,1,0}}) custom-call(bf16[64,2047,'
            f'128]{{2,1,0}} %bitcast.2142), custom_call_target='
            f'"tpu_custom_call", operand_layout_constraints={{}}')


@pytest.mark.parametrize("pattern, name", [
    (program_names.FLASH_FWD_EVENT, "flash_fwd"),
    (program_names.FLASH_BWD_DQ_EVENT, "flash_bwd_dq"),
    (program_names.FLASH_BWD_DKV_EVENT, "flash_bwd_dkv")])
def test_kernel_patterns_match_their_instruction_and_no_other(pattern, name):
    for instruction in (name, name + ".7", name + ".123"):
        assert re.search(pattern, _call(instruction))
        # the accepted flash metrics keep reading the renamed kernels
        assert re.search(kernels.FLASH_EVENT, _call(instruction))
    others = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} - {name}
    for other in sorted(others) + [name + "_2.1", "x" + name + ".1",
                                   "attn.32", "fusion.5"]:
        assert not re.search(pattern, _call(other)), other
    # the name alone is not enough: it has to be the Mosaic call
    assert not re.search(pattern, f"%{name}.7 = bf16[8] fusion(%p), "
                                  f"calls=%fused_computation")


@pytest.mark.parametrize("path", NAMED or [None])
def test_named_trace_splits_the_flash_time_by_kernel(path):
    assert path is not None, "no recorded step with the kernels' names"
    kept, record = _recorded(path)
    layers = kept["layers"]
    each = {name: _reader(name)(record) for name in FLASH_READERS}
    assert all(v is not None and v > 0 for v in each.values()), each
    whole_ms = 1e3 * kernels.flash_seconds(record["trace"])
    assert sum(each.values()) == pytest.approx(whole_ms, rel=1e-6)
    assert whole_ms * 1e6 == pytest.approx(kept["expect"]["flash_ns"])
    events = record["trace"]["devices"][0]
    counts = [sum(1 for e in events if re.search(p, e[0])) for p in (
        program_names.FLASH_FWD_EVENT, program_names.FLASH_BWD_DQ_EVENT,
        program_names.FLASH_BWD_DKV_EVENT)]
    # forward twice a layer (remat), each backward kernel once
    assert counts == [2 * layers, layers, layers]
    assert sum(counts) == kept["expect"]["flash_events"]
    # the lump's two readers are what they were
    assert _reader("flash.busy_share_pct")(record) == pytest.approx(
        100 * whole_ms / 1e3 / record["trace"]["busy_s"])


def test_old_names_read_nothing():
    _, record = _recorded(OLD)
    assert kernels.flash_seconds(record["trace"]) > 0      # %attn.<n>
    for name in FLASH_READERS:
        assert _reader(name)(record) is None
    for trace in (None, {}, {"devices": {}, "steps": 3},
                  {"devices": {0: []}, "steps": 0}):
        assert program_names.kernel_ms_per_step(
            trace, program_names.FLASH_FWD_EVENT) is None


def test_kernel_time_is_a_mean_over_chips_and_steps():
    one = [(_call("flash_fwd.1"), 0.0, 4e6), (_call("flash_fwd.2"), 5e6, 2e6),
           (_call("flash_bwd_dq.1"), 8e6, 1e6), ("%fusion.1 = x", 9e6, 7e6)]
    two = [(_call("flash_fwd.1"), 0.0, 2e6)]
    trace = {"devices": {0: one, 1: two}, "steps": 2}
    assert program_names.kernel_ms_per_step(
        trace, program_names.FLASH_FWD_EVENT) == pytest.approx(
            (6.0 + 2.0) / 2 / 2)
    assert program_names.kernel_ms_per_step(
        trace, program_names.FLASH_BWD_DQ_EVENT) == pytest.approx(0.25)
    assert program_names.kernel_ms_per_step(
        trace, program_names.FLASH_BWD_DKV_EVENT) is None


# ---------------------------------------------------------------------------
# the set-up readers on a hand-built account
# ---------------------------------------------------------------------------

Row = namedtuple("Row", "event fun_name at value")


def _event(key):
    from dtdl_tpu.runtime.compile_cache import ACCOUNT_EVENTS
    return next(e for e, k in ACCOUNT_EVENTS.items() if k == key)


TRACE, LOWER, COMPILE, RETRIEVAL, HITS, MISSES = map(_event, (
    "compile_trace_s", "compile_lower_s", "compile_backend_s",
    "compile_cache_retrieval_s", "compile_cache_hits",
    "compile_cache_misses"))


def _account(monkeypatch, rows):
    from dtdl_tpu.runtime import compile_cache
    monkeypatch.setattr(compile_cache, "compile_account", lambda: list(rows))


ROWS = [
    Row(TRACE, "silu", 101.5, 0.25),                    # inside the next
    Row(TRACE, "lm_train_step", 102.0, 2.0),
    Row(LOWER, "jit(lm_train_step)", 104.0, 1.5),
    Row(RETRIEVAL, None, 105.0, 0.75),                  # inside the next
    Row(HITS, None, 105.0, 1),
    Row(COMPILE, "jit(lm_train_step)", 105.5, 1.25),
    Row(TRACE, "readings", 106.0, 0.5),                 # another program
    Row(COMPILE, "jit(readings)", 108.0, 2.0),
    Row(MISSES, None, 108.0, 1),
    Row(HITS, None, 109.0, 1),
    # after the window's start: a compile in the window is not set-up
    Row(TRACE, "late", 121.0, 0.5),
    Row(COMPILE, "jit(late)", 122.0, 0.5),
    Row(MISSES, None, 122.0, 1),
]


def test_setup_readers_on_a_hand_built_account(monkeypatch):
    _account(monkeypatch, ROWS)
    record = {"window": {"start": 120.0}}
    assert _reader("setup.trace_lower_s")(record) == pytest.approx(2.0 + 1.5)
    assert _reader("setup.compile_load_s")(record) == pytest.approx(1.25)
    assert _reader("setup.other_programs_s")(record) == pytest.approx(
        0.5 + 2.0)
    assert _reader("setup.cache_hit_pct")(record) == pytest.approx(
        100 * 2 / 3)
    # an earlier cut leaves later rows out
    early = {"window": {"start": 104.5}}
    assert _reader("setup.trace_lower_s")(early) == pytest.approx(3.5)
    assert _reader("setup.other_programs_s")(early) == pytest.approx(0.0)
    assert _reader("setup.compile_load_s")(early) is None
    assert _reader("setup.cache_hit_pct")(early) is None


@pytest.mark.parametrize("rows, record", [
    (ROWS, {}),                                     # no window in the record
    (ROWS, {"window": {"steps": 3}}),               # no start
    (ROWS, {"window": {"start": 50.0}}),            # every row is later
    ([], {"window": {"start": 120.0}}),             # no rows
], ids=["no_window", "no_start", "all_later", "no_rows"])
def test_setup_readers_return_nothing_where_there_is_nothing(
        monkeypatch, rows, record):
    _account(monkeypatch, rows)
    for name in SETUP_READERS:
        assert _reader(name)(record) is None


def test_no_hits_and_no_misses_is_nothing_never_a_division(monkeypatch):
    _account(monkeypatch, [r for r in ROWS if r.event not in (HITS, MISSES)])
    record = {"window": {"start": 120.0}}
    assert _reader("setup.cache_hit_pct")(record) is None
    assert _reader("setup.trace_lower_s")(record) is not None
    _account(monkeypatch, [r for r in ROWS if r.event in (HITS, MISSES)])
    for name in SETUP_READERS[:3]:
        assert _reader(name)(record) is None
    # a process that compiled no step: nothing of the step's, all the rest
    _account(monkeypatch, [r for r in ROWS if r.fun_name in (
        "readings", "jit(readings)")])
    assert _reader("setup.trace_lower_s")(record) is None
    assert _reader("setup.compile_load_s")(record) is None
    assert _reader("setup.other_programs_s")(record) == pytest.approx(2.5)


def test_a_program_that_keeps_no_account_reads_nothing(monkeypatch):
    from dtdl_tpu.obs import trace
    from dtdl_tpu.runtime import compile_cache
    monkeypatch.delattr(compile_cache, "compile_account")
    monkeypatch.delattr(trace, "STEP_NAMES")    # as the parent of PR 25
    for name in SETUP_READERS:
        assert _reader(name)({"window": {"start": 1e12}}) is None


def test_rehearsed_traced_line_carries_the_setup_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "olmo7b-train-b2s2048", "--seed", str(2 ** 31 + 77), "--seconds",
         "0.5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    metrics, spans = line["metrics"], line["notes"]["setup_spans_s"]
    assert line["correct"] is True
    for name in SETUP_READERS[:3]:
        assert metrics[name]["unit"] == "s" and metrics[name]["value"] > 0
    assert 0 <= metrics["setup.cache_hit_pct"]["value"] <= 100
    # what jax reports of set-up lies inside the benchmark's own spans, and
    # the step's share inside the spans around the step's own calls
    assert sum(metrics[name]["value"] for name in SETUP_READERS[:3]) <= sum(
        spans[k] for k in ("plan", "make_state", "lower", "compile",
                           "warmup"))
    assert (0.5 * spans["lower"] <= metrics["setup.trace_lower_s"]["value"]
            <= spans["lower"])
    assert metrics["setup.compile_load_s"]["value"] <= spans["compile"]
    # no device line on the CPU: the kernels' readers leave theirs out
    assert not set(FLASH_READERS) & set(metrics)


# ---------------------------------------------------------------------------
# the manifest's entries
# ---------------------------------------------------------------------------

def _manifest(path):
    with open(path) as f:
        return json.load(f)


def test_manifest_entries_of_the_kernel_metrics():
    manifest = _manifest(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in FLASH_READERS:
        m = by_name[name]
        assert (m["moves"], m["source"], m["layer"], m["better"]) == (
            "train_tokens_per_s", "device_trace", "flash kernels", "lower")
        # every training cell goes through a runner that refuses a step
        # without a Mosaic kernel: the flash metrics list no cells
        assert "workloads" not in m and "workloads" not in by_name[
            "flash_roofline"]


def test_manifest_entries_of_the_setup_metrics():
    """The four set-up entries move ``setup_s``, in every cell (PR 27 took
    them out of a manifest of their own into BENCHMARK.json)."""
    manifest = _manifest(os.path.join(ROOT, "BENCHMARK.json"))
    entries = [m for m in manifest["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in entries] == list(SETUP_READERS)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in entries:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert os.path.isfile(
            os.path.join(BENCH, "metrics", m["name"] + ".py"))
        assert (m["moves"], m["source"], m["layer"]) == (
            "setup_s", "program_counter", "runtime / compile")
        assert m["layer"] in perf and m["name"] in perf


# ---------------------------------------------------------------------------
# tools/scope_dump.py: its reductions (the run itself needs the chip)
# ---------------------------------------------------------------------------

HLO = '''HloModule jit_lm_train_step, entry_computation_layout={()->()}

%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  ROOT %multiply.3 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(lm_train_step)/update/mul" source_file="x.py" source_line=3}
}

ENTRY %main.1 (Arg_0.1: bf16[8]) -> bf16[8] {
  %fusion.337 = bf16[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(lm_train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/rematted_computation/block_3/mlp/wg/dot_general" source_file="y.py"}
  %flash_fwd.2 = bf16[8]{0} custom-call(%fusion.337), custom_call_target="tpu_custom_call", metadata={op_name="jit(lm_train_step)/jvp(TransformerLM)/block_0/attn/flash_fwd/pallas_call"}
  %copy.9 = bf16[8]{0} copy(%flash_fwd.2)
  ROOT fusion.400 = bf16[8]{0} fusion(%copy.9), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(lm_train_step)/update/add"}
}
'''


def test_scope_dump_maps_instructions_to_components():
    from dtdl_tpu.obs.trace import device_component
    from tools import scope_dump

    stacks = scope_dump.stacks_from_hlo(HLO)
    assert set(stacks) == {"multiply.3", "fusion.337", "flash_fwd.2",
                           "fusion.400"}
    assert stacks["fusion.400"] == "jit(lm_train_step)/update/add"
    assert scope_dump.instruction_of(
        "%fusion.337 = bf16[8]{0} fusion(%Arg_0.1)") == "fusion.337"
    assert scope_dump.instruction_of("copy.9") == "copy.9"
    assert scope_dump.looks_like_a_stack("jit(f)/jvp(g)/mul")
    assert not scope_dump.looks_like_a_stack("fusion.337")
    assert not scope_dump.looks_like_a_stack(17)

    events = [("%fusion.337 = bf16[8] fusion(...)", 0.0, 4e6),
              ("%flash_fwd.2 = bf16[8] custom-call(...)", 4e6, 2e6),
              ("%copy.9 = bf16[8] copy(...)", 6e6, 1e6),
              ("%fusion.400 = bf16[8] fusion(...)", 7e6, 3e6),
              ("%fusion.337 = bf16[8] fusion(...)", 10e6, 4e6)]
    rows, loose = scope_dump.by_component(
        events, lambda n: stacks.get(scope_dump.instruction_of(n)), 2,
        device_component)
    assert rows == [["mlp", "recompute", 4.0], ["update", "update", 1.5],
                    ["flash", "forward", 1.0], ["unattributed", "-", 0.5]]
    assert loose == [["copy", "no op_name", 0.5]]
    assert sum(r[2] for r in rows) == pytest.approx(14.0 / 2)
