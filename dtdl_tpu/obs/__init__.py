"""Unified observability: span tracing, recompile sentinel, goodput/MFU
accounting, latency percentiles — for train AND serve loops.

The production triad the ROADMAP's north star needs (traces, utilization
accounting, tail latencies), built to the PR-1 rule: nothing in here may
add a host↔device sync to a hot loop.  See each module's docstring:

trace      span("data"/"dispatch"/"drain") → Chrome trace JSON (Perfetto),
           window-settled device track, jax.profiler annotations,
           request-correlated flow events + request_timeline(rid), the
           audited span/event catalogs, and the catalogue of device-side
           names (scopes, kernel and step names) with device_component()
recompile  jit-cache sentinel: unexpected retraces are named, with the
           differing abstract args (warn / raise / silent)
goodput    analytic model FLOPs (LM from config, CNNs from netspec),
           chip peaks, per-window MFU / tokens-per-sec / vs-roofline
hist       streaming log-bucketed histogram: p50/p95/p99 in fixed memory
observer   the Observer facade every loop takes (~3 lines per call site)
export     boundary-sampled continuous metrics: JSONL series, Prometheus
           text + opt-in http scrape endpoint, window-delta sources
slo        declarative SLO targets over the exported series: error
           budgets, burn-rate alerts, crossings as trace events

Quick start::

    from dtdl_tpu.obs import Observer, GoodputMeter, lm_train_flops

    obs = Observer(trace_path="trace.json",
                   goodput=GoodputMeter(
                       flops_per_step=lm_train_flops(model, bs, seq),
                       tokens_per_step=bs * (seq - 1)))
    train_epoch(step, state, loader, strategy, reporter=rep, observer=obs)
    obs.close()                       # writes the Perfetto-loadable trace
"""

from dtdl_tpu.obs.export import (  # noqa: F401
    JsonlSeriesSink, MetricsExporter, PrometheusSink, prometheus_text,
)
from dtdl_tpu.obs.goodput import (  # noqa: F401
    GoodputMeter, lm_decode_flops, lm_forward_flops, lm_prefill_flops,
    lm_train_flops, lm_verify_flops, netspec_flops, peak_flops_per_chip,
)
from dtdl_tpu.obs.hist import LogHistogram  # noqa: F401
from dtdl_tpu.obs.observer import NULL_OBSERVER, Observer  # noqa: F401
from dtdl_tpu.obs.recompile import (  # noqa: F401
    RecompileError, RecompileEvent, RecompileSentinel,
)
from dtdl_tpu.obs.slo import (  # noqa: F401
    SLO, SLOEvaluator, default_train_slos,
)
from dtdl_tpu.obs.trace import (  # noqa: F401
    DEVICE_SCOPES, EVENT_CATALOG, NULL_TRACER, SPAN_CATALOG, Tracer,
    corr_rid, device_component, proc_tag, set_proc_tag,
)
