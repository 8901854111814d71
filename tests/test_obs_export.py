"""Fleet-era observability (ISSUE 11): correlated tracing, continuous
export, SLO layer.

The contracts:

1. **request correlation** — every request-scoped event carries the
   USER rid (+ attempt ``arid``/``lineage``), ``request_timeline(rid)``
   reconstructs one request's story across threads, and a hedged,
   failed-over request under deterministic fault injection shows BOTH
   sibling attempts and the winner in one timeline (the acceptance
   scenario);
2. **continuous export** — window-delta snapshots at drain/harvest
   boundaries into JSONL/Prometheus sinks (+ an opt-in scrape
   endpoint), with the PR 9 fleet accounting invariant holding in the
   *exported series* (the deltas telescope to the final books), not
   just the end-of-run summary;
3. **SLO layer** — declarative targets over the exported series;
   injected TTFT regression and availability breach (fault plan) emit
   burn-rate crossings as BOTH trace events and exported series
   fields;
4. the satellites: the span/event catalog audit (names emitted anywhere
   in dtdl_tpu/ must be cataloged), ``window()`` delta semantics with
   the cumulative ``summary()`` contract untouched, and the shared
   ``error_kind`` helper over all five kinds.
"""

import json
import pathlib
import re
import time
from http.client import HTTPConnection
from types import SimpleNamespace

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dtdl_tpu
from dtdl_tpu.models.transformer import transformer_lm
from dtdl_tpu.obs import (JsonlSeriesSink, MetricsExporter, Observer,
                          PrometheusSink, SLO, SLOEvaluator, Tracer,
                          prometheus_text)
from dtdl_tpu.obs.trace import (EVENT_CATALOG, SPAN_CATALOG, corr_rid,
                                proc_tag)
from dtdl_tpu.resil import FaultPlan
from dtdl_tpu.resil.faults import replica_site
from dtdl_tpu.serve import (ERROR_KINDS, FleetMetrics, InferenceEngine,
                            Request, Router, Scheduler, ServeMetrics,
                            default_fleet_slos, error_kind)
from dtdl_tpu.serve.health import STATES

MAX_SEQ = 32
N_NEW = 6


@pytest.fixture(scope="module")
def engine():
    model = transformer_lm(
        "tiny", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
        d_ff=64, max_seq=MAX_SEQ, attn_impl="dense", dtype=jnp.float32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 4), jnp.int32))["params"])
    return InferenceEngine(model, params, n_slots=2, buckets=(8,))


def mk_prompts(n, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, int(rng.integers(3, 8))).tolist()
            for _ in range(n)]


@pytest.fixture(scope="module")
def oracle(engine):
    """Fault-free greedy reference; also warms the compiled programs so
    the threaded tests never hold a worker inside a first compile."""
    prompts = mk_prompts(6)
    refs = [Request(list(p), N_NEW) for p in prompts]
    Scheduler(engine, harvest_lag=1).run(refs)
    return prompts, [r.tokens for r in refs]


def kw(**over):
    base = dict(sched_kwargs={"harvest_lag": 1}, retry_budget=3,
                probe_interval_s=0.01, watchdog_s=0.25)
    base.update(over)
    return base


class _ListSink:
    def __init__(self):
        self.points = []

    def write(self, point):
        self.points.append(dict(point))

    def close(self):
        pass


# ---------------------------------------------------------------------------
# satellites: error_kind, window() deltas, catalog audit
# ---------------------------------------------------------------------------

def test_error_kind_all_five_kinds():
    """The one shared parser of the ``<kind>: reason`` grammar — every
    kind the scheduler can stamp, plus the non-error cases."""
    assert ERROR_KINDS == ("rejected", "expired", "failed", "aborted",
                           "shed")
    for kind in ERROR_KINDS:
        assert error_kind(f"{kind}: something bad") == kind
        # prefix must be exact: a kind buried mid-string is not a kind
        assert error_kind(f"x {kind}: y") is None
    assert error_kind(None) is None
    assert error_kind("") is None
    assert error_kind("no prefix here") is None
    # the scheduler's canonical list IS this list (no drift)
    assert Scheduler._ERROR_KINDS is ERROR_KINDS


def test_serve_metrics_window_deltas_and_cumulative_summary():
    m = ServeMetrics(n_slots=2)
    req = SimpleNamespace(rid=1)
    for _ in range(3):
        m.on_submit(req)
    m.on_harvest_tokens(10)
    w1 = m.window()
    assert w1["requests_submitted"] == 3
    assert w1["decode_tokens"] == 10
    # second window: only what happened since
    m.on_submit(req)
    m.on_harvest_tokens(5)
    w2 = m.window()
    assert w2["requests_submitted"] == 1
    assert w2["decode_tokens"] == 5
    # an idle window is all-zero deltas, not a repeat of the last one
    w3 = m.window()
    assert w3["requests_submitted"] == 0 and w3["decode_tokens"] == 0
    # the cumulative summary() contract is untouched by windowing
    s = m.summary()
    assert s["requests_submitted"] == 4 and s["decode_tokens"] == 15
    # nothing non-scalar leaks into a series point
    assert all(isinstance(v, (int, float)) for v in w2.values())
    assert "spec_steps_by_k" not in w2


def test_fleet_metrics_window_deltas():
    fm = FleetMetrics()
    for _ in range(4):
        fm.on_submit()
    fm.on_reject()
    w1 = fm.window()
    assert w1["fleet_requests_submitted"] == 5     # reject counts submit
    assert w1["fleet_requests_rejected"] == 1
    w2 = fm.window()
    assert w2["fleet_requests_submitted"] == 0
    # gauges pass through at current value (bool -> int)
    assert w2["fleet_accounting_ok"] in (0, 1)
    s = fm.summary()
    assert s["fleet_requests_submitted"] == 5      # cumulative intact
    assert "replicas" not in w2 and "replica_health" not in w2


def test_event_catalog_audit_no_silent_drift():
    """Every literal name passed to .span(/.event(/.instant( anywhere
    in dtdl_tpu/ must be cataloged, and every catalog entry must have
    an emitter — the catalog lagged emitters twice before PR 9
    (trainer_rollback was the live example this audit caught)."""
    pkg = pathlib.Path(dtdl_tpu.__file__).parent
    pat = re.compile(r"\.(span|event|instant)\(\s*(f?)\"([^\"]+)\"")
    spans, events = set(), set()
    for py in pkg.rglob("*.py"):
        for m in pat.finditer(py.read_text()):
            kind, is_f, name = m.group(1), m.group(2), m.group(3)
            if is_f:
                # the one sanctioned dynamic pattern: replica_{state}
                # over the health-machine states; anything else must
                # use a literal name or extend this audit
                assert name == "replica_{state}", (
                    f"{py.name}: un-auditable dynamic {kind} name "
                    f"{name!r}")
                names = {name.replace("{state}", s) for s in STATES}
            else:
                assert "{" not in name
                names = {name}
            (spans if kind == "span" else events).update(names)
    assert spans == SPAN_CATALOG, (
        f"uncataloged spans: {sorted(spans - SPAN_CATALOG)}; "
        f"stale catalog entries: {sorted(SPAN_CATALOG - spans)}")
    assert events == EVENT_CATALOG, (
        f"uncataloged events: {sorted(events - EVENT_CATALOG)}; "
        f"stale catalog entries: {sorted(EVENT_CATALOG - events)}")


def test_device_scope_catalog_audit_no_silent_drift():
    """The same audit for the device-side names: every
    ``named_scope("...")`` literal under dtdl_tpu/ is a key of
    DEVICE_SCOPES (or re-enters another catalogue's scope by name:
    REENTERED_SCOPES) and every key has a scope in the source; every
    ``pallas_call`` carries a ``name=`` line and the names are
    KERNEL_NAMES' keys; the functions train/step.py hands to
    ``strategy.compile*`` are STEP_NAMES.  Device time and the compile
    account are read by these names, so a scope, a kernel or a step added
    without its catalogue entry would go unattributed in silence."""
    from dtdl_tpu.obs.trace import (_MOE_MODULES, DEVICE_SCOPES, KERNEL_NAMES,
                                    REENTERED_SCOPES, STEP_NAMES)
    pkg = pathlib.Path(dtdl_tpu.__file__).parent
    scope_pat = re.compile(r"named_scope\(\s*(f?)\"(\w[^\"]*)\"")
    # one name a call, or two where a call has a windowed twin
    # (``name="flash_fwd" if band is None else "flash_swa_fwd",``)
    name_pat = re.compile(
        r"^\s+name=\"(\w+)\"(?: if [\w ]+ else \"(\w+)\")?,$", re.M)
    scopes, kernels = set(), set()
    for py in pkg.rglob("*.py"):
        text = py.read_text()
        for m in scope_pat.finditer(text):
            assert not m.group(1), f"{py.name}: dynamic scope {m.group(2)!r}"
            scopes.add(m.group(2))
        calls = len(re.findall(r"\bpl\.pallas_call\(", text))
        if calls:
            names = name_pat.findall(text)
            assert len(names) == calls, (
                f"{py.name}: {calls} pallas_call(s), {len(names)} name= "
                f"lines")
            kernels.update(n for pair in names for n in pair if n)
    # a scope re-entered by name is another catalogue's: a module's, or
    # jax's own for recomputation
    assert REENTERED_SCOPES <= scopes
    assert REENTERED_SCOPES - {"rematted_computation"} <= set(_MOE_MODULES)
    scopes -= REENTERED_SCOPES
    assert scopes == set(DEVICE_SCOPES), (
        f"uncataloged scopes: {sorted(scopes - set(DEVICE_SCOPES))}; "
        f"stale catalog entries: {sorted(set(DEVICE_SCOPES) - scopes)}")
    assert kernels == set(KERNEL_NAMES), (
        f"uncataloged kernels: {sorted(kernels - set(KERNEL_NAMES))}; "
        f"stale catalog entries: {sorted(set(KERNEL_NAMES) - kernels)}")
    steps = set(re.findall(r"strategy\.compile\w*\((\w+)\)",
                           (pkg / "train" / "step.py").read_text()))
    assert steps == set(STEP_NAMES), (
        f"uncataloged steps: {sorted(steps - set(STEP_NAMES))}; "
        f"stale catalog entries: {sorted(set(STEP_NAMES) - steps)}")


# ---------------------------------------------------------------------------
# exporter: sources -> sinks, prometheus text, scrape endpoint
# ---------------------------------------------------------------------------

def test_exporter_sources_sinks_and_throttle(tmp_path):
    path = str(tmp_path / "series.jsonl")
    sink = _ListSink()
    exp = MetricsExporter(sinks=[JsonlSeriesSink(path), sink],
                          interval_s=60.0)
    state = {"n": 0}

    def src():
        state["n"] += 1
        return {"count": state["n"], "ok": True, "name": "skipme",
                "nested": {"x": 1}}

    exp.add_source("fleet", src)
    p1 = exp.sample(force=True)
    assert p1["fleet_count"] == 1
    assert p1["fleet_ok"] == 1                   # bool -> int
    assert "fleet_name" not in p1                # strings dropped
    assert "fleet_nested" not in p1              # nested dropped
    # throttled: inside interval_s nothing is sampled (sources unread)
    assert exp.sample() is None
    assert state["n"] == 1
    assert exp.sample(force=True)["fleet_count"] == 2
    exp.close()
    lines = [json.loads(l) for l in open(path)]
    assert [p["fleet_count"] for p in lines] == [1, 2]
    assert sink.points[-1]["fleet_count"] == 2
    # a broken source is counted and skipped, never fatal
    exp2 = MetricsExporter()
    exp2.add_source("bad", lambda: 1 / 0)
    exp2.add_source("good", lambda: {"v": 7})
    pt = exp2.sample(force=True)
    assert pt["good_v"] == 7 and exp2.source_errors == 1
    # ...and so is a broken sink (disk full mid-run): the point still
    # reaches the healthy sinks and the sample call never raises into
    # the serving loop that invoked it
    ok_sink = _ListSink()

    class _BrokenSink:
        def write(self, point):
            raise OSError("disk full")

        def close(self):
            pass

    exp3 = MetricsExporter(sinks=[_BrokenSink(), ok_sink])
    exp3.add_source("", lambda: {"v": 1})
    assert exp3.sample(force=True)["v"] == 1
    assert exp3.sink_errors == 1 and ok_sink.points


def test_prometheus_text_format():
    text = prometheus_text({"t": 1700000000.0, "fleet_ttft_s_p99": 0.25,
                            "ok": True, "skip me": 3, "name": "x"})
    lines = text.strip().splitlines()
    assert "# TYPE dtdl_fleet_ttft_s_p99 gauge" in lines
    assert "dtdl_fleet_ttft_s_p99 0.25 1700000000000" in lines
    assert "dtdl_ok 1 1700000000000" in lines
    assert "dtdl_skip_me 3 1700000000000" in lines  # sanitized name
    assert not any("name" in l and "x" in l for l in lines)
    assert prometheus_text({}) == ""


def test_prometheus_scrape_endpoint():
    exp = MetricsExporter(interval_s=0.0)
    exp.add_source("", lambda: {"requests_finished": 42})
    try:
        port = exp.serve_http(port=0)
        assert exp.port == port
        exp.sample(force=True)
        conn = HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.status == 200
        assert "0.0.4" in resp.getheader("Content-Type")
        assert "dtdl_requests_finished 42" in body
        conn.request("GET", "/other")
        assert conn.getresponse().status == 404
        conn.close()
    finally:
        exp.close()


# ---------------------------------------------------------------------------
# SLO layer (pure: synthetic points, injected clock)
# ---------------------------------------------------------------------------

def test_slo_gauge_breach_recovery_and_events():
    tracer = Tracer()
    obs = Observer(trace=tracer, sentinel=None)
    slo = SLO("ttft_p99", metric="ttft_s_p99", op="<=", target=0.1)
    ev = SLOEvaluator([slo], observer=obs)
    out = ev.evaluate({"ttft_s_p99": 0.05}, now=0.0)
    assert out["slo_ttft_p99_ok"] == 1
    assert out["slo_ttft_p99_burn"] == pytest.approx(0.5)
    # regression: value doubles past target -> breach + burn crossing
    out = ev.evaluate({"ttft_s_p99": 0.2}, now=1.0)
    assert out["slo_ttft_p99_ok"] == 0
    assert out["slo_ttft_p99_burn"] == pytest.approx(2.0)
    names = [e["name"] for e in tracer.to_chrome()["traceEvents"]]
    assert "slo_breach" in names and "slo_burn_rate" in names
    # recovery emits once, and crossing counters are monotone receipts
    out = ev.evaluate({"ttft_s_p99": 0.05}, now=2.0)
    assert out["slo_ttft_p99_ok"] == 1
    names = [e["name"] for e in tracer.to_chrome()["traceEvents"]]
    assert names.count("slo_recovered") == 1
    assert ev.summary() == {"slo_breach_events": 1,
                            "slo_burn_crossings": 1,
                            "slo_ttft_p99_ok": 1}
    # a point without the metric is no verdict, not a breach
    assert ev.evaluate({}, now=3.0) == {}
    # crossings count WITHOUT an observer too: summary() is the
    # monitor's rollup, a missing tracer must not zero the books
    blind = SLOEvaluator([SLO("x", metric="m", op="<=", target=1.0)])
    blind.evaluate({"m": 5.0}, now=0.0)
    assert blind.summary()["slo_breach_events"] == 1
    assert blind.summary()["slo_burn_crossings"] == 1
    # a >= objective collapsing to 0 burns at the finite cap, never
    # inf — every exported point must stay strict JSON
    from dtdl_tpu.obs.slo import BURN_CAP
    floor = SLOEvaluator([SLO("acc", metric="rate", op=">=",
                              target=0.5)])
    out = floor.evaluate({"rate": 0.0}, now=0.0)
    assert out["slo_acc_burn"] == BURN_CAP
    json.dumps(out)                       # would raise on Infinity
    # gate: an always-present-at-zero input skips judgment entirely
    gated = SLOEvaluator([SLO("acc", metric="spec_acceptance_rate",
                              op=">=", target=0.5,
                              gate="spec_drafted_tokens")])
    assert gated.evaluate({"spec_acceptance_rate": 0.0,
                           "spec_drafted_tokens": 0}, now=0.0) == {}
    out = gated.evaluate({"spec_acceptance_rate": 0.25,
                          "spec_drafted_tokens": 8}, now=1.0)
    assert out["slo_acc_ok"] == 0


def test_slo_ratio_rolling_window_and_burn():
    tracer = Tracer()
    obs = Observer(trace=tracer, sentinel=None)
    slo = SLO("availability", good="fin", bad=("fail", "exp"),
              target=0.9, window_s=10.0)
    ev = SLOEvaluator([slo], observer=obs)
    out = ev.evaluate({"fin": 8, "fail": 0, "exp": 0}, now=0.0)
    assert out["slo_availability_sli"] == 1.0
    assert out["slo_availability_burn"] == 0.0
    # 2 bad of 10 in-window -> sli 0.8 < 0.9, burn = 0.2/0.1 = 2x
    out = ev.evaluate({"fin": 0, "fail": 1, "exp": 1}, now=1.0)
    assert out["slo_availability_sli"] == pytest.approx(0.8)
    assert out["slo_availability_burn"] == pytest.approx(2.0)
    assert out["slo_availability_ok"] == 0
    names = [e["name"] for e in tracer.to_chrome()["traceEvents"]]
    assert "slo_burn_rate" in names
    # the window ROLLS: the bad events age out past window_s
    out = ev.evaluate({"fin": 5}, now=20.0)
    assert out["slo_availability_sli"] == 1.0
    assert out["slo_availability_ok"] == 1
    # declaration validation is loud
    with pytest.raises(ValueError):
        SLO("x", target=0.9)                     # neither mode
    with pytest.raises(ValueError):
        SLO("x", metric="m", good="g", bad="b", target=0.9)
    with pytest.raises(ValueError):
        SLO("x", good="g", bad="b", target=1.5)  # ratio needs (0,1)
    with pytest.raises(ValueError):
        SLOEvaluator([SLO("a", metric="m", target=1),
                      SLO("a", metric="m", target=1)])


# ---------------------------------------------------------------------------
# request-correlated tracing on the real scheduler / fleet
# ---------------------------------------------------------------------------

def test_scheduler_request_timeline_and_receipts(engine, oracle):
    """Standalone scheduler: one request's timeline reads intake →
    admit → first token → finished in order, with flow markers, and
    the full pipeline adds no compiled programs (the zero-recompile
    receipt with observability ON)."""
    prompts, want = oracle
    obs = Observer(trace=True, sentinel="raise")
    exp = MetricsExporter(interval_s=0.0)
    sched = Scheduler(engine, harvest_lag=1, observer=obs, exporter=exp)
    reqs = [Request(list(p), N_NEW) for p in prompts]
    sched.run(reqs)
    for r, toks in zip(reqs, want):
        assert r.error is None and r.tokens == toks
    tl = obs.request_timeline(reqs[0].rid)
    names = [e["name"] for e in tl if e.get("ph") in ("i", "X")]
    assert names[0] == "prefill"                 # the admission span
    for a, b in (("request_admitted", "request_first_token"),
                 ("request_first_token", "request_finished")):
        assert names.index(a) < names.index(b), names
    # correlation args: standalone requests are their own origin,
    # and rids land in the proc-tagged wire form (round 17) so
    # multi-host traces merge without collisions
    admitted = next(e for e in tl if e["name"] == "request_admitted")
    assert admitted["args"]["rid"] == corr_rid(reqs[0].rid)
    assert admitted["args"]["arid"] == corr_rid(reqs[0].rid)
    assert admitted["args"]["rid"].startswith(proc_tag() + "/")
    assert admitted["args"]["lineage"] == "primary"
    # flow chain: a start and an end for this rid
    flows = [e for e in tl if e.get("cat") == "request"]
    assert [f["ph"] for f in flows][0] == "s"
    assert [f["ph"] for f in flows][-1] == "f"
    # another request's timeline never bleeds in
    assert all(e["args"]["rid"] == corr_rid(reqs[0].rid)
               for e in tl if "args" in e and "rid" in e.get("args", {}))
    # boundary-sampled export happened, orders of magnitude below
    # per-token rate; and no program was compiled by the pipeline
    assert 1 <= exp.n_snapshots <= sched.step_count + 2
    stats = engine.compile_stats()
    assert stats["decode"] == 1 and list(stats["prefill"].values()) == [1]


@pytest.mark.fleet
@pytest.mark.faults
def test_hedged_failover_single_correlated_timeline(engine, oracle):
    """THE acceptance scenario: replica 0's engine dies on every call,
    hedging re-submits to replica 1, the hedge wins.  One
    request_timeline(rid) must show BOTH sibling attempts (distinct
    arids, lineage primary vs hedge) and the winner, and the flow
    chain must close."""
    prompts, want = oracle
    plan = FaultPlan()
    for k in range(50):
        plan.at(replica_site(0, "engine"), k)
    obs = Observer(trace=True)
    with Router(engine, n_replicas=2, plan=plan, auto_restart=False,
                observer=obs, hedge_after_s=0.0,
                # as in the exported-series scenario below: at kw()'s 0.25 s
                # a busy machine starves the healthy replica past the stall
                # watchdog, which evicts the one replica the hedges need
                **kw(recover_after=50, watchdog_s=5.0)) as router:
        reqs = router.run([Request(list(p), N_NEW) for p in prompts])
        s = router.summary()
    for r, toks in zip(reqs, want):
        assert r.error is None and r.tokens == toks
    assert s["fleet_accounting_ok"] and s["fleet_hedges"] >= 1
    # find a hedged request whose primary landed on the dead replica
    probe = None
    for r in reqs:
        tl = obs.request_timeline(r.rid)
        lineages = {e["args"]["lineage"]: e for e in tl
                    if e.get("args", {}).get("lineage")}
        if {"primary", "hedge"} <= set(lineages):
            probe, timeline, by_lineage = r, tl, lineages
            break
    assert probe is not None, "no request was hedged"
    names = [e["name"] for e in timeline]
    assert names[0] == "request_submitted"
    assert "request_hedged" in names
    # both sibling attempts present, distinct, joined under ONE rid
    arids = {e["args"]["arid"] for e in timeline
             if "arid" in e.get("args", {})}
    assert len(arids) == 2
    assert all(e["args"]["rid"] == corr_rid(probe.rid)
               for e in timeline if "rid" in e.get("args", {}))
    # the terminal event names the WINNER and the attempt count
    done = next(e for e in timeline if e["name"] == "request_done")
    assert done["args"]["kind"] == "finished"
    assert done["args"]["attempts"] == 2
    assert done["args"]["hedged"] == 1
    assert done["args"]["arid"] in arids
    # the winner is the attempt that actually finished decoding
    finished = [e for e in timeline if e["name"] == "request_finished"]
    assert done["args"]["arid"] in {e["args"]["arid"] for e in finished}
    # Chrome-trace flow events: one start, steps, one closing end
    flows = [e["ph"] for e in timeline if e.get("cat") == "request"]
    assert flows[0] == "s" and flows[-1] == "f" and "t" in flows
    # events from at least two distinct threads joined into one story
    assert len({e["tid"] for e in timeline}) >= 2
    # causal order: submit strictly precedes every dispatch — the
    # intake event is emitted under the router lock the pump needs
    ts = {e["name"]: e["ts"] for e in timeline}
    assert ts["request_submitted"] <= ts["request_dispatched"]


def test_standalone_error_terminal_closes_flow_chain(engine):
    """A standalone request whose flow chain opened at admission must
    close it on EVERY terminal, not just the happy path: expiry after
    admission and cancel-in-slot both end with a flow 'f' event."""
    obs = Observer(trace=True)
    sched = Scheduler(engine, harvest_lag=1, observer=obs)
    expired = Request(mk_prompts(1, seed=30)[0], 20, deadline_s=30.0)
    cancelled = Request(mk_prompts(1, seed=31)[0], 20)
    sched.submit(expired)
    sched.submit(cancelled)
    sched.step()                              # both admitted
    expired.deadline_at = time.perf_counter() - 1.0
    sched.step()                              # watchdog expires it
    sched.cancel(cancelled.rid, "test")
    sched.run()
    assert error_kind(expired.error) == "expired"
    assert error_kind(cancelled.error) == "aborted"
    for req in (expired, cancelled):
        flows = [e["ph"] for e in obs.request_timeline(req.rid)
                 if e.get("cat") == "request"]
        assert flows and flows[0] == "s" and flows[-1] == "f", \
            (req, flows)


@pytest.mark.fleet
def test_rejected_intake_timeline_has_no_dangling_flow(engine, oracle):
    """An intake-time rejection never started a flow chain: its
    timeline is the terminal marker alone — no flow 'end' without a
    'start' (which would render as a broken arrow in Perfetto)."""
    prompts, _ = oracle
    obs = Observer(trace=True)
    router = Router(engine, n_replicas=1, observer=obs,
                    **kw(poll_s=0.05, probe_interval_s=1.0))
    try:
        router.shutdown()
        late = router.submit(Request(list(prompts[0]), N_NEW))
        assert late.error.startswith("rejected:")
        tl = obs.request_timeline(late.rid)
        done = [e for e in tl if e["name"] == "request_done"]
        assert len(done) == 1
        assert done[0]["args"]["kind"] == "rejected"
        assert not [e for e in tl if e.get("cat") == "request"]
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# exporter + SLO on the failover e2e (the series-invariant satellite)
# ---------------------------------------------------------------------------

@pytest.mark.fleet
@pytest.mark.faults
def test_failover_e2e_exported_series_holds_invariant(engine, oracle,
                                                      tmp_path):
    """The PR 9 failover-oracle e2e re-run with the exporter + SLO
    evaluator attached: every request still completes oracle-identical,
    and the ``submitted == finished+rejected+expired+failed+aborted``
    invariant holds in the EXPORTED SERIES — the window deltas
    telescope exactly to the settled books, so a monitor consuming the
    series sees the same truth as the final summary."""
    prompts, want = oracle
    plan = FaultPlan()
    for k in range(50):
        plan.at(replica_site(0, "engine"), k)
    path = str(tmp_path / "series.jsonl")
    exp = MetricsExporter(sinks=[JsonlSeriesSink(path)], interval_s=0.0)
    with Router(engine, n_replicas=2, plan=plan, auto_restart=False,
                exporter=exp,
                slos=default_fleet_slos(ttft_p99_s=60.0,
                                        availability=0.5),
                # the stall watchdog is not what this test is about, and at
                # kw()'s 0.25 s a busy machine starves the healthy replica
                # past it, which evicts the one replica the requests need
                **kw(recover_after=50, watchdog_s=5.0)) as router:
        reqs = router.run([Request(list(p), N_NEW) for p in prompts])
    s = router.summary()
    for r, toks in zip(reqs, want):
        assert r.error is None and r.tokens == toks, r
    assert s["fleet_retries"] >= 1 and s["fleet_accounting_ok"]
    pts = [json.loads(l) for l in open(path)]
    assert len(pts) >= 2
    terms = ("finished", "rejected", "expired", "failed", "aborted")
    sums = {k: sum(p.get(f"fleet_requests_{k}", 0) for p in pts)
            for k in ("submitted",) + terms}
    # the invariant IN THE SERIES, not just the final summary
    assert sums["submitted"] == sum(sums[k] for k in terms), sums
    assert sums["submitted"] == 6 and sums["finished"] == 6
    # and the series agrees with the cumulative books
    assert sums["finished"] == s["fleet_requests_finished"]
    # the SLO layer judged the same points (clean run: no crossings)
    assert any("slo_availability_ok" in p for p in pts)
    assert s["slo_breach_events"] == 0
    assert s["export_snapshots"] == len(pts)


# ---------------------------------------------------------------------------
# SLO detection under injected regressions (the acceptance scenario)
# ---------------------------------------------------------------------------

@pytest.mark.fleet
@pytest.mark.faults
def test_slo_detects_injected_ttft_regression(engine, oracle):
    """A loop-site stall (0.35s, watchdog disarmed) delays every first
    token past a 50ms TTFT target: the evaluator must emit the breach
    + burn-rate crossing as trace events AND as fields of an exported
    series point."""
    prompts, _ = oracle
    plan = FaultPlan().at(replica_site(0, "loop"), 0, kind="stall",
                          seconds=0.35)
    obs = Observer(trace=True)
    sink = _ListSink()
    exp = MetricsExporter(sinks=[sink], interval_s=0.0)
    with Router(engine, n_replicas=1, plan=plan, observer=obs,
                exporter=exp, slos=default_fleet_slos(ttft_p99_s=0.05),
                sched_kwargs={"harvest_lag": 1}, retry_budget=0,
                probe_interval_s=0.01, watchdog_s=30.0) as router:
        reqs = router.run([Request(list(p), N_NEW) for p in prompts])
        s = router.summary()
    assert all(r.error is None for r in reqs)     # slow, not broken
    assert s["fleet_evictions"] == 0              # watchdog disarmed
    assert s["slo_breach_events"] >= 1
    assert s["slo_burn_crossings"] >= 1
    assert s["slo_ttft_p99_ok"] == 0
    names = [e["name"] for e in obs.tracer.to_chrome()["traceEvents"]]
    assert "slo_breach" in names and "slo_burn_rate" in names
    breached = [p for p in sink.points
                if p.get("slo_ttft_p99_ok") == 0]
    assert breached and breached[-1]["slo_ttft_p99_burn"] > 1.0


@pytest.mark.fleet
@pytest.mark.faults
def test_slo_detects_injected_availability_breach(engine, oracle):
    """Every replica's engine dead + zero retry budget: every request
    fails, availability collapses, and the burn-rate crossing lands in
    both the trace and the exported series."""
    prompts, _ = oracle
    plan = FaultPlan()
    for i in (0, 1):
        for k in range(200):
            plan.at(replica_site(i, "engine"), k)
    obs = Observer(trace=True)
    sink = _ListSink()
    exp = MetricsExporter(sinks=[sink], interval_s=0.0)
    with Router(engine, n_replicas=2, plan=plan, auto_restart=False,
                observer=obs, exporter=exp,
                slos=default_fleet_slos(availability=0.999),
                **kw(retry_budget=0, evict_after=100,
                     recover_after=1)) as router:
        reqs = router.run([Request(list(p), N_NEW)
                           for p in prompts[:3]], timeout_s=60)
        s = router.summary()
    for r in reqs:
        assert r.error is not None and error_kind(r.error) == "failed"
    assert s["fleet_requests_failed"] == 3 and s["fleet_accounting_ok"]
    assert s["slo_breach_events"] >= 1
    assert s["slo_burn_crossings"] >= 1
    assert s["slo_availability_ok"] == 0
    names = [e["name"] for e in obs.tracer.to_chrome()["traceEvents"]]
    assert "slo_breach" in names and "slo_burn_rate" in names
    bad = [p for p in sink.points if p.get("slo_availability_ok") == 0]
    assert bad
    # total outage at a 99.9% target burns at ~1000x — the point the
    # paging math in SCALING.md round 16 hangs on
    assert bad[-1]["slo_availability_burn"] >= 100
    assert bad[-1]["slo_availability_sli"] == 0.0
