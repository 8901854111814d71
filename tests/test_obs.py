"""Observability subsystem (dtdl_tpu/obs): tier-1 guardrails.

1. **tracer** — spans nest, are thread-safe, and export valid
   Chrome-trace-event JSON (the Perfetto contract);
2. **recompile sentinel** — fires exactly once per genuine retrace,
   never on cache hits, and names the function + the differing abstract
   args (the acceptance criterion: a deliberately shape-unstable step fn
   is caught by name);
3. **histogram** — streaming log-bucketed percentiles track numpy's
   within the bucket resolution, in fixed memory;
4. **goodput** — the analytic LM FLOP count matches a hand-derived
   number for the 'tiny' config within 1% (the matmul-only
   convention of obs/goodput.py), and MFU follows from it;
5. **integration** — `train_epoch` with the FULL observer enabled still
   performs at most one host sync per log window (the PR-1 contract,
   re-pinned with the tests/test_async_metrics.py sync-counting
   harness), and serve percentiles come from already-harvested host
   floats (zero added per-token syncs).
"""

import json
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dtdl_tpu.metrics.report import Reporter
from dtdl_tpu.obs import (GoodputMeter, LogHistogram, NULL_OBSERVER,
                          Observer, RecompileError, RecompileSentinel,
                          Tracer, lm_train_flops, netspec_flops)


# ---------------------------------------------------------------------------
# 1. tracer
# ---------------------------------------------------------------------------

def test_spans_nest_and_export_valid_chrome_json(tmp_path):
    t = Tracer()
    with t.span("outer", phase="epoch"):
        time.sleep(0.002)
        with t.span("inner"):
            time.sleep(0.002)
        time.sleep(0.002)
    t.device_window("device", seconds=0.004, steps=2)
    path = t.save(str(tmp_path / "trace.json"))

    with open(path) as f:
        trace = json.load(f)
    assert trace["displayTimeUnit"] == "ms"
    events = {e["name"]: e for e in trace["traceEvents"]
              if e.get("ph") == "X"}
    assert set(events) == {"outer", "inner", "device"}
    for e in events.values():   # the Chrome trace-event 'X' contract
        assert {"ts", "dur", "pid", "tid"} <= set(e)
    outer, inner = events["outer"], events["inner"]
    # nesting: the child interval is contained in the parent's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert outer["dur"] >= 6000 * 0.5            # us; generous for CI jitter
    # span args survive export
    assert outer["args"]["phase"] == "epoch"
    # the settled device window lives on its own named track
    assert events["device"]["tid"] != outer["tid"]
    assert events["device"]["args"]["steps"] == 2
    names = [e for e in trace["traceEvents"] if e.get("ph") == "M"]
    assert any("device" in m["args"]["name"] for m in names)


def test_tracer_gzip_and_event_cap(tmp_path):
    t = Tracer(max_events=5)
    for i in range(9):
        with t.span(f"s{i}"):
            pass
    assert len(t) == 5 and t.dropped == 4
    path = t.save(str(tmp_path / "trace.json.gz"))
    import gzip
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    assert trace["otherData"]["dropped_events"] == 4


def test_tracer_thread_safe():
    t = Tracer()
    barrier = threading.Barrier(4)   # overlap all threads (distinct idents)

    def work():
        barrier.wait()
        for _ in range(50):
            with t.span("w"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    evs = [e for e in t.to_chrome()["traceEvents"] if e.get("ph") == "X"]
    assert len(evs) == 200
    assert len({e["tid"] for e in evs}) == 4     # one track per thread


# ---------------------------------------------------------------------------
# 2. recompile sentinel
# ---------------------------------------------------------------------------

def test_sentinel_fires_once_per_retrace_never_on_hits():
    s = RecompileSentinel(policy="silent")
    f = s.watch(jax.jit(lambda x: x * 2), "double")
    f(jnp.zeros(4))             # first compile: inside the budget
    assert s.events == []
    f(jnp.zeros(4))             # cache hit
    f(jnp.zeros((4,)))          # cache hit (same abstract signature)
    assert s.events == []
    f(jnp.zeros(8))             # genuine retrace
    assert len(s.events) == 1
    f(jnp.zeros(8))             # hit on the new shape: no new event
    assert len(s.events) == 1
    e = s.events[0]
    assert e.name == "double"
    assert e.diff == {"args[0]": "float32[4] -> float32[8]"}
    assert "double" in e.message() and "float32[8]" in e.message()


def test_sentinel_catches_shape_unstable_train_step(devices):
    """Acceptance pin: a deliberately shape-unstable step fn is caught,
    named, and the differing abstract args are reported."""
    from dtdl_tpu.models import MLP
    from dtdl_tpu.parallel import SingleDevice
    from dtdl_tpu.train import init_state, make_train_step
    import optax

    strategy = SingleDevice()
    state = strategy.replicate(init_state(
        MLP(n_units=8), jax.random.PRNGKey(0), jnp.zeros((1, 16)),
        optax.sgd(0.1)))
    sentinel = RecompileSentinel(policy="silent")
    step = sentinel.watch(make_train_step(strategy), "train_step")

    def batch(bs):
        return {"image": jnp.zeros((bs, 16)),
                "label": jnp.zeros((bs,), jnp.int32)}

    state, _ = step(state, batch(8))
    state, _ = step(state, batch(8))          # hit
    assert sentinel.events == []
    state, _ = step(state, batch(12))         # the unstable batch shape
    assert len(sentinel.events) == 1
    msg = sentinel.events[0].message()
    assert "train_step" in msg
    assert "float32[8,16] -> float32[12,16]" in msg
    assert sentinel.summary() == {"recompile_events": 1,
                                  "recompiled_fns": ["train_step"]}


def test_sentinel_rewatch_resumes_compile_count():
    """Loops re-wrap the step fn every epoch/leg; the compile budget
    belongs to the underlying jit, so an epoch-2 retrace still fires."""
    s = RecompileSentinel(policy="silent")
    jitted = jax.jit(lambda x: x * 3)
    f1 = s.watch(jitted, "f")
    f1(jnp.zeros(4))             # compile #1: inside the budget
    f2 = s.watch(jitted, "f")    # fresh wrapper (as train_epoch does)
    f2(jnp.zeros(6))             # genuine retrace — must NOT be absorbed
    assert len(s.events) == 1
    assert s.events[0].diff == {"args[0]": "float32[4] -> float32[6]"}
    # re-watching a wrapper unwraps it instead of double-counting
    f3 = s.watch(f2, "f")
    assert f3._fn is jitted


def test_sentinel_raise_policy_and_expected_budget():
    s = RecompileSentinel(policy="raise")
    f = s.watch(jax.jit(lambda x: x + 1), "inc", expected=2)
    f(jnp.zeros(2))
    f(jnp.zeros(3))             # second compile: still inside expected=2
    with pytest.raises(RecompileError, match="inc"):
        f(jnp.zeros(4))
    # non-jit callables pass through unwrapped
    plain = lambda x: x  # noqa: E731
    assert s.watch(plain) is plain
    with pytest.raises(ValueError):
        RecompileSentinel(policy="bogus")


# ---------------------------------------------------------------------------
# 3. histogram
# ---------------------------------------------------------------------------

def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-4.0, sigma=1.0, size=20_000)  # latency-shaped
    h = LogHistogram()
    h.extend(xs)
    # bounded relative error: one bucket ratio (10**(1/64) ~ 3.7%)
    tol = 10 ** (1.0 / h.bins_per_decade) - 1.0
    for p in (50, 90, 95, 99):
        ref = np.percentile(xs, p)
        assert abs(h.percentile(p) - ref) / ref <= tol, (p, ref)
    assert h.n == len(xs)
    assert h.min == xs.min() and h.max == xs.max()
    np.testing.assert_allclose(h.mean, xs.mean(), rtol=1e-9)


def test_histogram_fixed_memory_and_clamping():
    h = LogHistogram(lo=1e-3, hi=1e3, bins_per_decade=10)
    n_buckets = len(h._counts)
    h.add(1e-9)                  # below lo: clamps into the first bucket
    h.add(1e9)                   # above hi: clamps into the last
    h.add(0.0)                   # non-positive: clamps to lo
    assert len(h._counts) == n_buckets
    # percentiles never escape the observed extremes despite clamping
    assert h.percentile(0) >= 0.0
    assert h.percentile(100) <= 1e9
    assert h.summary("x_")["x_count"] == 3


def test_histogram_merge_and_validation():
    a, b = LogHistogram(), LogHistogram()
    a.extend([0.01, 0.02])
    b.extend([0.04, 0.08])
    a.merge(b)
    assert a.n == 4 and a.max == 0.08
    with pytest.raises(ValueError):
        a.merge(LogHistogram(bins_per_decade=7))
    with pytest.raises(ValueError):
        a.percentile(101)
    with pytest.raises(ValueError):
        LogHistogram(lo=1.0, hi=0.1)
    assert LogHistogram().summary() == {}       # empty: no fields


# ---------------------------------------------------------------------------
# 4. goodput / MFU accounting
# ---------------------------------------------------------------------------

def test_lm_flops_match_hand_derived_tiny_within_1pct():
    """The roofline-doc convention, derived here by hand for 'tiny'
    (vocab 256, d_model 64, 2 layers, 4 heads x head_dim 16, d_ff 128)
    at bs=8, seq=128 — i.e. t=127 predicted positions."""
    from dtdl_tpu.models import transformer_lm
    model = transformer_lm("tiny")
    B, t, D, V, F, L, H, hd = 8, 127, 64, 256, 128, 2, 4, 16
    per_tok = (
        L * (8 * D * D            # q,k,v,o projections: 4 matmuls, 2 FLOP/MAC
             + 4 * H * t * hd * 0.5   # qk^T + att*v, causal half
             + 6 * D * F)         # SwiGLU: wi, wg, wo
        + 2 * D * V)              # lm head
    hand_fwd = B * t * per_tok
    hand_train = 3.0 * hand_fwd   # fwd + 2x bwd
    got = lm_train_flops(model, 8, 128)
    assert abs(got - hand_train) / hand_train < 0.01
    # and MFU follows: hand flops over a known window and a fake peak
    meter = GoodputMeter(flops_per_step=got, tokens_per_step=8 * 127,
                         peak_flops=1e12)
    w = meter.window(steps=4, seconds=2.0)
    hand_mfu = hand_train * 4 / 2.0 / 1e12
    assert abs(w["mfu"] - hand_mfu) / hand_mfu < 0.01
    assert w["tokens_per_sec"] == pytest.approx(8 * 127 * 4 / 2.0)
    assert w["steps_per_sec"] == pytest.approx(2.0)


def test_goodput_meter_windows_and_totals():
    m = GoodputMeter(flops_per_step=1e9, samples_per_step=64,
                     peak_flops=1e12, roofline_mfu=0.5)
    assert m.window(0, 1.0) == {}                # degenerate: no fields
    w1 = m.window(10, 1.0)
    m.window(10, 3.0)
    assert w1["mfu"] == pytest.approx(0.01)
    assert w1["vs_roofline"] == pytest.approx(0.02)
    assert w1["samples_per_sec"] == pytest.approx(640.0)
    tot = m.totals()
    assert tot["steps_per_sec"] == pytest.approx(20 / 4.0)
    # peak_flops=None disables MFU outright; throughput still reported
    cpu = GoodputMeter(flops_per_step=1e9, peak_flops=None)
    w = cpu.window(2, 1.0)
    assert "mfu" not in w and w["achieved_tflops"] == pytest.approx(0.002)
    # the "auto" default detects the local chip (None on this CPU box)
    assert GoodputMeter().peak_flops is None


def test_netspec_flops_hand_check(tmp_path):
    net = tmp_path / "net.prototxt"
    net.write_text("""
name: "tiny"
layer { name: "data" type: "Input" top: "data" }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "c1"
  convolution_param { num_output: 4 kernel_size: 3 stride: 1 pad: 1 } }
layer { name: "pool1" type: "Pooling" bottom: "c1" top: "p1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "fc" type: "InnerProduct" bottom: "p1" top: "out"
  inner_product_param { num_output: 10 } }
""")
    got = netspec_flops(str(net), (8, 8, 1))
    # conv: 2*3*3*1*4*8*8 MACs-as-FLOPs + bias 4*8*8; pool: 8->4;
    # fc: 2*(4*4*4)*10 + 10
    hand = (2 * 9 * 1 * 4 * 64 + 4 * 64) + (2 * 64 * 10 + 10)
    assert got == hand
    assert netspec_flops(str(net), (8, 8, 1), backward=True) == 3 * hand


# ---------------------------------------------------------------------------
# 5. integration: observer in the loops, serve percentiles
# ---------------------------------------------------------------------------

def test_train_epoch_with_observer_keeps_one_sync_per_window(devices):
    """Acceptance pin: the FULL observer (tracer + sentinel + goodput)
    adds zero host↔device syncs — conversions still happen only at the
    log-window boundaries (the test_async_metrics.py harness)."""
    import optax
    from test_async_metrics import SyncCounter, TrackedScalar
    from dtdl_tpu.data.loader import DataLoader
    from dtdl_tpu.models import MLP
    from dtdl_tpu.parallel import SingleDevice
    from dtdl_tpu.train import init_state, make_train_step, train_epoch

    strategy = SingleDevice()
    steps, log_interval = 24, 8
    rng = np.random.default_rng(0)
    loader = DataLoader(
        {"image": rng.normal(size=(steps * 8, 32)).astype(np.float32),
         "label": rng.integers(0, 10, steps * 8).astype(np.int64)},
        8, shuffle=False)
    state = strategy.replicate(init_state(
        MLP(n_units=16), jax.random.PRNGKey(0), jnp.zeros((1, 32)),
        optax.sgd(0.05)))
    real_step = make_train_step(strategy)
    counter = SyncCounter()

    def tracked_step(state, batch):
        counter.dispatched += 1
        state, metrics = real_step(state, batch)
        return state, {k: TrackedScalar(v, counter)
                       for k, v in metrics.items()}

    payloads = []

    class _Sink:
        def write(self, payload):
            payloads.append(payload)

        def close(self):
            pass

    obs = Observer(trace=True, sentinel="warn",
                   goodput=GoodputMeter(flops_per_step=1e9,
                                        tokens_per_step=8,
                                        peak_flops=1e12))
    train_epoch(tracked_step, state, loader, strategy,
                reporter=Reporter([_Sink()], leader_only=False),
                log_interval=log_interval, observer=obs)

    floats = [e for e in counter.events if e[1] == "float"]
    assert len(floats) == steps * 2              # every metric, exactly once
    boundaries = {1, 9, 17, steps}
    assert counter.sync_points <= boundaries, (
        f"observer added a sync between log boundaries: "
        f"{sorted(counter.sync_points - boundaries)}")
    # goodput fields rode the existing boundary reports
    window_payloads = [p for p in payloads if "mfu" in p]
    assert len(window_payloads) == 3             # one per log boundary
    assert all(p["tokens_per_sec"] > 0 for p in window_payloads)
    # the tracer saw the host phases and the settled device windows
    names = {e["name"] for e in obs.tracer.to_chrome()["traceEvents"]}
    assert {"data", "dispatch", "drain", "device"} <= names
    # step-time tails accumulated from settled windows only
    assert obs.summary()["step_time_s_count"] == 4   # 3 boundaries + tail
    assert obs.sentinel.events == []             # stable shapes: no firing


def test_observer_facade_null_and_save(tmp_path):
    # the null observer is free: shared no-op context, identity watch
    with NULL_OBSERVER.span("x"):
        pass
    assert NULL_OBSERVER.window(5, 1.0) == {}
    assert NULL_OBSERVER.summary() == {}
    f = jax.jit(lambda x: x)
    assert NULL_OBSERVER.watch(f) is f
    assert NULL_OBSERVER.save() is None
    # a real observer writes its trace on close() / context exit
    path = str(tmp_path / "t.json")
    with Observer(trace_path=path) as obs:
        with obs.span("phase"):
            pass
    with open(path) as fh:
        assert any(e["name"] == "phase"
                   for e in json.load(fh)["traceEvents"])


def test_serve_metrics_percentiles_from_harvested_floats():
    """Serve tails come from the SAME lag-harvested host floats as the
    means — a pure-host path (zero added per-token device syncs), and
    the percentiles track numpy on the recorded values."""
    from types import SimpleNamespace
    from dtdl_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics(n_slots=4)
    rng = np.random.default_rng(1)
    ttfts = rng.lognormal(-3, 0.6, 200)       # ~50ms scale, latency-shaped
    lats = rng.lognormal(-6, 0.4, 200)
    for ttft, lat in zip(ttfts, lats):
        # on_first_token stamps its own clock; a t_submit placed `ttft`
        # in the past yields that TTFT to within the loop's microseconds
        req = SimpleNamespace(t_submit=time.perf_counter() - ttft,
                              tokens=[1, 2, 3], t_first=0.0,
                              t_done=2 * lat)
        m.on_first_token(req)
        m.on_finish(req)                      # (t_done - t_first) / 2 = lat
    s = m.summary()
    tol = 10 ** (1.0 / m.ttft_hist.bins_per_decade) - 1 + 1e-3
    for p in (50, 95, 99):
        ref = np.percentile(m.ttft_s, p)
        assert abs(s[f"ttft_s_p{p}"] - ref) / ref <= tol
        ref = np.percentile(m.tok_latency_s, p)
        assert abs(s[f"tok_latency_s_p{p}"] - ref) / ref <= tol
    assert s["ttft_s_count"] == 200


# ---------------------------------------------------------------------------
# 6. satellites: report sinks + script shim
# ---------------------------------------------------------------------------

def test_reporter_context_manager_closes_sinks_on_exception(tmp_path):
    from dtdl_tpu.metrics.report import JsonlSink
    path = str(tmp_path / "log.jsonl")
    with pytest.raises(RuntimeError):
        with Reporter([JsonlSink(path)], leader_only=False) as rep:
            rep.report({"step": 0, "loss": 1.0})
            raise RuntimeError("mid-train crash")
    with open(path) as f:
        rec = json.loads(f.readline())
    assert rec["loss"] == 1.0
    # sinks are context managers on their own too
    with JsonlSink(str(tmp_path / "l2.jsonl")) as sink:
        sink.write({"a": 1})
    assert sink._f.closed


def test_tensorboard_warning_fires_once(caplog, monkeypatch, tmp_path):
    import logging
    import dtdl_tpu.metrics.report as report
    # force the no-writer path hermetically (a None sys.modules entry
    # makes the import raise immediately — and skips the ~20s torch
    # import this box would otherwise pay)
    for mod in ("torch", "torch.utils.tensorboard", "tensorboardX"):
        monkeypatch.setitem(__import__("sys").modules, mod, None)
    monkeypatch.setattr(report, "_TB_WARNED", False)
    with caplog.at_level(logging.WARNING, logger="dtdl_tpu"):
        a = report.TensorBoardSink(str(tmp_path / "tb1"))
        b = report.TensorBoardSink(str(tmp_path / "tb2"))
    assert a._writer is None and b._writer is None
    warnings = [r for r in caplog.records
                if "no tensorboard writer" in r.message]
    assert len(warnings) == 1        # per process, not per instantiation
    # degraded sinks still accept writes/close silently
    b.write({"step": 1, "loss": 1.0})
    b.close()
