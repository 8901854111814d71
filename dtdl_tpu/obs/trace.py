"""Span tracer: host-side phase timing as Chrome trace events.

The PR-1 async discipline made the loops opaque on purpose — between
boundaries the host only enqueues work, so wall-clock prints no longer
say where host time goes (data? dispatch? the drain?).  This tracer is
the host-side complement of ``jax.profiler`` (which sees the *device*
ops): lightweight ``span("data") / span("dispatch") / span("drain")``
context managers record complete ('X') events on the calling thread
(the serve scheduler adds ``admit`` / ``harvest`` and, under
speculative decoding, ``draft`` — host time inside the DraftSource —
and ``verify`` — the k-wide verify dispatch, args carrying the step's
draft width; the fleet Router adds ``route`` around its dispatch
round).  The resil layer marks its recoveries as zero-duration
:meth:`Tracer.instant` events (``guard_bad_step`` / ``guard_rollback``
/ ``trainer_preempted`` / ``request_expired`` / ``request_cancelled``
/ ``engine_failure`` / ``scheduler_shutdown``, via ``Observer.event``),
and the fleet layer its health/lifecycle edges (``replica_suspect`` /
``replica_evicted`` / ``replica_draining`` / ``replica_restarted`` /
``request_retry`` / ``request_hedged`` / ``hedge_won`` /
``router_shutdown``), so a trace shows exactly where a run skipped,
rolled back, shed load, or failed over.  Everything is
thread-safe for the serve scheduler, exported as Chrome-trace-event JSON
that Perfetto / ``chrome://tracing`` loads directly.

Two honesty rules, inherited from SCALING.md "Async dispatch
discipline":

* a span measures **host phases only** — entering/leaving a span never
  touches the device, so tracing cannot add a sync (pinned by the
  sync-counting test in tests/test_obs.py);
* device time appears only as **window-settled** spans
  (:meth:`Tracer.device_window`): once a drain has settled a log window,
  the window's wall time is recorded on a synthetic "device" track —
  late by one window, exact in total, never a per-step round-trip.

When a ``jax.profiler`` capture is active, each span also opens a
``jax.profiler.TraceAnnotation`` (a TraceMe: ~ns while no capture
runs) so host phases line up with XLA ops inside one Perfetto view.

**Request correlation (round 16).**  Fleet-era serving spreads one user
request over many threads — router intake, a pump dispatch, one worker
per attempt (retries and hedges are *sibling* attempts) — and anonymous
spans cannot be joined back into the request's story.  Every
request-scoped event therefore carries correlation args: ``rid`` (the
USER request id, stable across attempts), ``arid`` (the replica-local
attempt id), and on dispatch a ``lineage`` field (``primary`` /
``retry:N`` after N burned retries / ``requeue`` for a free
backpressure re-dispatch / ``hedge``).  :meth:`Tracer.flow` adds Chrome-trace flow
events (``ph`` s/t/f sharing ``id=rid``) so Perfetto draws the arrows
from submit through every attempt to the winning completion, and
:meth:`Tracer.request_timeline` reconstructs the same story
programmatically — the ordered list of every recorded event correlated
with one rid, whichever thread emitted it.

The span/event catalogs below (:data:`SPAN_CATALOG` /
:data:`EVENT_CATALOG`) are the single source of truth for names emitted
anywhere in dtdl_tpu; tests/test_obs_export.py audits the source tree
against them, so the catalog can no longer silently lag a new emitter
(it did twice between PR 5 and PR 9).

**Device-side names.**  The host spans above never reach the device; what
a ``jax.profiler`` capture shows of the device is named by the program at
compile time, at no run-time cost: ``jax.named_scope`` around the train
step's own phases (:data:`DEVICE_SCOPES`, audited like the span catalog),
flax's module scopes inside the blocks (:data:`MODULE_SCOPES`), a ``name=``
on every Pallas kernel (:data:`KERNEL_NAMES`) and a name of its own on each
jitted step (:data:`STEP_NAMES`).  :func:`device_component` is the one map
from an op's name stack to ``(component, pass)``.
"""

from __future__ import annotations

import contextlib
import gzip
import json
import os
import re
import threading
import time

from jax.profiler import TraceAnnotation

# synthetic track ids inside the exported trace: host spans carry the
# real thread id; settled device windows live on their own track
DEVICE_TID = 1

# ---------------------------------------------------------------------------
# the span/event catalog — every name emitted through Observer.span /
# Observer.event / Tracer.instant anywhere in dtdl_tpu/.  Audited against
# the source tree by tests/test_obs_export.py: add the name HERE when you
# add an emitter, or the audit fails by name.
# ---------------------------------------------------------------------------

SPAN_CATALOG = frozenset({
    # training loops (PR 3)
    "data", "dispatch", "drain",
    # serve scheduler (PR 2/4): admission, drafting, the k-wide verify
    # dispatch, the lag harvest, and the per-admission prefill call
    "admit", "draft", "verify", "harvest", "prefill",
    # fleet router (PR 9)
    "route",
})

EVENT_CATALOG = frozenset({
    # resil (PR 5); trainer_rollback was emitted since PR 5 but missing
    # from the documented catalog until the round-16 audit pinned it —
    # exactly the drift the audit test exists to stop
    "guard_bad_step", "guard_rollback", "trainer_preempted",
    "trainer_rollback",
    # serve scheduler containment + lifecycle (PR 5/6)
    "request_expired", "request_cancelled", "engine_failure",
    "scheduler_shutdown", "page_pool_shed",
    # fleet health/lifecycle edges (PR 9); replica_* names are emitted as
    # f"replica_{state}" over the health-machine states
    "replica_suspect", "replica_evicted", "replica_draining",
    "replica_healthy", "replica_restarted", "replica_drain_timeout",
    "request_retry", "request_hedged", "hedge_won", "router_shutdown",
    "router_drain_timeout", "router_pump_error",
    # request-correlated lifecycle (round 16): intake → dispatch →
    # admit → first token → terminal, every one carrying rid/arid
    "request_submitted", "request_dispatched", "request_admitted",
    "request_first_token", "request_finished", "request_done",
    # SLO layer (round 16)
    "slo_breach", "slo_recovered", "slo_burn_rate",
    # chunked prefill / disaggregation (round 19): the page-granular
    # KV migration (side=extract on the prefill replica, side=inject
    # on the decode one) and the Router's stage transition between them
    "kv_handoff", "request_migrated",
    # elastic training plane (round 17): peer detection, world
    # re-formation, shrink-to-survivors restore, generation fencing —
    # every abort/fence/shed on the failure path surfaces here, never
    # as a silent hang
    "elastic_peer_lost", "elastic_rendezvous", "elastic_restore",
    "elastic_snapshot", "elastic_stale_fenced", "elastic_step_timeout",
    # TCP control-plane store (round 18): every socket-level recovery
    # edge of the coordinator protocol — a reconnect after a dead
    # socket, a torn reply frame detected by name, an amnesiac
    # coordinator refused by epoch, and a WAL rehydration on the
    # server side
    "store_reconnect", "store_torn_frame", "store_epoch_refused",
    "store_wal_recovered",
    # multi-tenant serving (round 22): LoRA adapter-bank residency
    # edges, grammar-constraint outcomes (reason=illegal is a contained
    # failure, reason=incomplete a budget truncation mid-structure),
    # and incremental TokenStream deliveries at harvest boundaries
    "adapter_loaded", "adapter_evicted", "grammar_violation",
    "stream_delivery",
    # hierarchical KV cache (round 23): a batch of evicted pages
    # spilled to the host/disk tiers, a prefix-miss served back out of
    # them, and the fleet prefix directory's routing/consistency edges
    # (a hit = affinity beat least-loaded; an invalidation = a replica
    # eviction/drain/containment delisted its advertised pages)
    "page_spilled", "page_restored", "prefix_directory_hit",
    "prefix_directory_invalidated",
})


# ---------------------------------------------------------------------------
# device-side names: what the program puts into the compiled step so that a
# device trace can be read by component.  All of it is compile-time
# metadata.  tests/test_obs_export.py holds DEVICE_SCOPES, KERNEL_NAMES
# and STEP_NAMES to the source tree (every ``named_scope("...")`` literal
# under dtdl_tpu/, and the reverse); tests/test_device_names.py holds
# MODULE_SCOPES to the model's own module tree and the lowered LM step to
# the whole catalogue.
# ---------------------------------------------------------------------------

# jax.named_scope literals, each a component of its own.  ``embed`` /
# ``head`` sit in TransformerLM.__call__ (the two ops outside any flax
# submodule); the rest in train/step.py.  With ``vocab_chunk_size > 0`` the
# head matmul runs inside the chunked loss, tile by tile, and so under
# ``loss``.
# ``gdn`` wraps the gated delta rule itself (ops/gated_delta.py: the
# chunk-local stage, kernels or batched matmuls, and the scan over chunks),
# ``kda`` the rule whose decay is a vector over the key channels (the same
# file's ``kda_rule``, the same two parts), ``moe_dispatch`` the held
# experts' routing plan, gather and weighted scatter-add
# (models/transformer.py:HeldExperts), ``gate`` the attention's sigmoid
# output gate (under ``attn``: the component ``attn_gate``).
DEVICE_SCOPES = frozenset({"embed", "head", "loss", "grad_sync", "update",
                           "guard", "gdn", "kda", "moe_dispatch", "gate"})

# scopes a function enters under a name that is another catalogue's, and
# that map as there: the held experts' grouped SwiGLU runs outside its flax
# module, inside the choice of buffer, and keeps the module's ``experts``
# (:data:`_MOE_MODULES`); a backward branch of that choice runs its forward
# again under the name jax gives a checkpoint's (``pass`` 'recompute')
REENTERED_SCOPES = frozenset({"experts", "rematted_computation"})

# flax module scopes of models/transformer.py (flax puts them there; this
# repo only names the modules) -> component
# (``ln_attn_out``, ``ln_mlp_out``: the norms on the sublayers' outputs of a
# block with ``post_norms``)
MODULE_SCOPES = {
    "attn": "attn_other", "mlp": "mlp", "moe": "moe",
    "ln_attn": "norm", "ln_mlp": "norm", "ln_f": "norm",
    "ln_attn_out": "norm", "ln_mlp_out": "norm",
}
_ATTN_PROJECTIONS = frozenset({"q", "k", "v", "out"})
# latent attention's (``kv_a``, ``kv_b``: its two steps to keys and values)
_MLA_PROJECTIONS = frozenset({"q", "kv_a", "kv_b", "out"})
# the sub-modules of the hybrid blocks, by the module they sit in: a Gated
# DeltaNet layer (flax scope ``gdn``, the same word as the delta rule's own
# scope inside it) and the held experts' layer (``moe``)
_GDN_MODULES = {"in_qkvz": "gdn_proj", "in_ba": "gdn_proj",
                "out": "gdn_proj", "conv": "gdn_conv", "norm": "gdn_other"}
# a Kimi Delta Attention layer (flax scope ``kda``, and the rule's own
# ``kda`` inside it, as above)
_KDA_MODULES = dict(
    {name: "kda_proj" for name in ("in_q", "in_k", "in_v", "in_b", "f_a",
                                   "f_b", "g_a", "g_b", "out")},
    conv="kda_conv", norm="kda_other")
_RULE_MODULES = {"gdn": _GDN_MODULES, "kda": _KDA_MODULES}
_MOE_MODULES = {"router": "moe_router", "shared": "moe_shared",
                "experts": "moe_experts"}
# (``gate_proj``: the output gate's projection of its own, ``gate='own'``)
_ATTN_OTHER = {"gate": "attn_gate", "q_norm": "norm", "k_norm": "norm",
               "kv_norm": "norm", "gate_proj": "attn_proj"}

# pallas_call ``name=`` -> component.  On the chip the kernel's HLO
# instruction takes this name (``%flash_fwd.<n> = ... custom-call(...)
# custom_call_target="tpu_custom_call"``), and it is a scope of the
# kernel's own ops in a name stack.
KERNEL_NAMES = {
    "flash_fwd": "flash", "flash_bwd_dq": "flash", "flash_bwd_dkv": "flash",
    # the same three under a window (a band under the diagonal): a component
    # of their own, so that a trace tells the windowed layers from the full
    "flash_swa_fwd": "flash_swa", "flash_swa_bwd_dq": "flash_swa",
    "flash_swa_bwd_dkv": "flash_swa",
    "paged_attn": "paged_attn",
    "moe_gmm": "moe_gmm", "moe_tgmm": "moe_gmm",
    "gdn_chunk_fwd": "gdn", "gdn_chunk_bwd": "gdn",
    "kda_chunk_fwd": "kda", "kda_chunk_bwd": "kda",
}

# names of the traced step functions of train/step.py: ``jit(<name>)`` in
# ``XLA Modules`` events, ``jax.log_compiles`` and the ``fun_name`` of jax's
# compile events, by which a reader of the compile account
# (runtime/compile_cache.py) tells the step's program from every other
STEP_NAMES = frozenset({"lm_train_step", "train_step", "eval_step",
                        "predict_step"})

_AFTER_BACKWARD = frozenset({"grad_sync", "update", "guard"})
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def device_component(name_stack: str):
    """``(component, pass)`` of an op from its name stack, the ``/``-joined
    string jax gives every op (``jit(lm_train_step)/transpose(jvp(
    TransformerLM))/jvp(TransformerLM)/checkpoint/rematted_computation/
    block_3/attn/q/dot_general``; the last element is the primitive).

    ``component`` is a member of :data:`DEVICE_SCOPES`, a value of
    :data:`MODULE_SCOPES` or :data:`KERNEL_NAMES`, ``attn_proj`` for
    ``attn/{q,k,v,out,gate_proj,kv_a,kv_b}``, ``attn_gate`` for
    ``attn/gate``; under
    a Gated DeltaNet module ``gdn_proj`` (``gdn/{in_qkvz,in_ba,out}``),
    ``gdn_conv``, ``gdn`` for the delta rule itself (``gdn/gdn``) and
    ``gdn_other`` for the rest; under a Kimi Delta Attention module the same
    four with ``kda`` (``kda_proj`` for its nine projections); under the
    held experts' ``moe`` the kernels' ``moe_gmm``,
    ``moe_dispatch``, ``moe_router``, ``moe_shared``, ``moe_experts``; or
    None where no catalogued scope is on the stack.  ``pass`` is ``update`` for what follows the backward pass
    (``grad_sync``, ``update``, ``guard``); else ``recompute`` under
    ``rematted_computation``, ``backward`` under a ``transpose(...)``,
    ``forward`` otherwise.  jax wraps a scope entered inside a transformed
    function in the transform's name (``jvp(loss)``,
    ``transpose(jvp(loss))``); the wrappers are read for the pass and
    stripped for the name."""
    names, transposed, rematted = [], False, False
    for element in name_stack.split("/"):
        here = False
        while (m := _WRAPPED.match(element)):
            here = here or m.group(1) == "transpose"
            element = m.group(2)
        transposed = transposed or here
        # ``transpose(rematted_computation)`` is the backward pass of what
        # was recomputed under a scope of that name (the held experts'
        # choice of buffer, models/transformer.py:_first_or_full)
        rematted = rematted or (element == "rematted_computation"
                                and not here)
        # flax names a module's method other than ``__call__`` as a scope
        # of its own (``attn/attn._grouped_attend/q``): not a component
        if not (names and element.startswith(names[-1] + ".")):
            names.append(element)
    component = None
    for i, name in enumerate(names):
        component = (name if name in DEVICE_SCOPES else
                     MODULE_SCOPES.get(name) or KERNEL_NAMES.get(name))
        if component is None:
            continue
        rest = names[i + 1:]
        kernels = [KERNEL_NAMES[n] for n in rest if n in KERNEL_NAMES]
        if name == "attn":
            if kernels:
                component = kernels[0]
            elif rest and rest[0] in _ATTN_PROJECTIONS | _MLA_PROJECTIONS:
                component = "attn_proj"
            elif rest and rest[0] in _ATTN_OTHER:
                component = _ATTN_OTHER[rest[0]]
        elif name in _RULE_MODULES:
            # the module's scope; the delta rule's own scope of the same
            # name inside it is the component ``gdn`` / ``kda``
            inner = rest[0] if rest else None
            component = (name if inner == name else
                         _RULE_MODULES[name].get(inner, name + "_other"))
        elif name == "moe":
            if kernels:
                component = kernels[0]
            elif "moe_dispatch" in rest:
                component = "moe_dispatch"
            else:
                # the first sub-module named: the experts' own ops sit
                # inside the choice of buffer (``moe/cond/branch_0_fun/
                # experts/...``)
                component = next((_MOE_MODULES[n] for n in rest
                                  if n in _MOE_MODULES), component)
        break
    if component in _AFTER_BACKWARD:
        return component, "update"
    if rematted:
        return component, "recompute"
    return component, "backward" if transposed else "forward"


# ---------------------------------------------------------------------------
# correlation ids (round 17): rids are prefixed with a process tag so
# multi-host traces (and elastic-training events from many workers)
# merge into one Perfetto view without id collisions — process A's
# request 7 ("p0/7") can never chain into process B's ("p1/7").
# ---------------------------------------------------------------------------

_PROC_TAG: str | None = None


def proc_tag() -> str:
    """This process's correlation-id prefix: ``DTDL_PROC_TAG`` when set
    (a router/launcher naming its workers), else ``p{process_index}``.
    Cached on first use; override early via :func:`set_proc_tag`."""
    global _PROC_TAG
    if _PROC_TAG is None:
        tag = os.environ.get("DTDL_PROC_TAG")
        if not tag:
            import jax
            tag = f"p{jax.process_index()}"
        _PROC_TAG = tag
    return _PROC_TAG


def set_proc_tag(tag: str | None) -> None:
    """Set (or with None, reset) the process tag — call before any
    correlated event is emitted; changing it mid-trace splits chains."""
    global _PROC_TAG
    _PROC_TAG = tag


def corr_rid(n) -> str:
    """The wire form of a correlation id: ``f"{proc_tag}/{n}"``.  Every
    emitter of a ``rid``/``arid`` arg or a request-flow id goes through
    here; already-prefixed strings pass through unchanged (the Router
    stamps attempt clones whose user rid was prefixed at intake)."""
    return n if isinstance(n, str) else f"{proc_tag()}/{n}"


class _Span:
    """One open span; records the 'X' event on exit."""

    __slots__ = ("tracer", "name", "args", "t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args
        self._ann = TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.tracer._record(self.name, self.t0, t1 - self.t0,
                            threading.get_ident(), self.args)
        return False


class Tracer:
    """Thread-safe span recorder with Chrome-trace-event export.

    ``max_events`` bounds memory: the buffer is a ring in spirit — once
    full, new events are dropped and ``dropped`` counts them (a trace
    that silently ate the heap would violate the observability budget
    it exists to enforce).
    """

    def __init__(self, max_events: int = 200_000):
        self.max_events = max_events
        self.dropped = 0
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._meta: dict = {"pid": os.getpid()}

    # ---- recording ----------------------------------------------------

    def span(self, name: str, **args) -> _Span:
        """Context manager timing one host phase on the calling thread."""
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event."""
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append({
                "name": name, "ph": "i", "s": "t",
                "ts": (time.perf_counter() - self._t0) * 1e6,
                "pid": self._meta["pid"],
                "tid": threading.get_ident(),
                **({"args": args} if args else {})})

    _FLOW_PH = {"start": "s", "step": "t", "end": "f"}

    def flow(self, name: str, fid, phase: str = "step",
             **args) -> None:
        """A Chrome-trace flow event: ``phase`` is ``start`` / ``step``
        / ``end`` and every event sharing (``name``, ``fid``) is joined
        into one arrow chain across threads — the Perfetto rendering of
        a request's path through router intake, dispatch, and each
        attempt's replica thread.  ``fid`` is the correlation id (the
        fleet uses the USER request rid in its proc-tagged
        :func:`corr_rid` wire form)."""
        ph = self._FLOW_PH.get(phase)
        if ph is None:
            raise ValueError(f"flow phase must be one of "
                             f"{sorted(self._FLOW_PH)}, got {phase!r}")
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            ev = {"name": name, "cat": "request", "ph": ph, "id": fid,
                  "ts": (time.perf_counter() - self._t0) * 1e6,
                  "pid": self._meta["pid"],
                  "tid": threading.get_ident()}
            if ph == "f":
                ev["bp"] = "e"     # bind the arrowhead to the enclosing
            if args:               # slice's end, the Perfetto convention
                ev["args"] = args
            self._events.append(ev)

    def request_timeline(self, rid) -> list[dict]:
        """Every recorded event correlated with USER request ``rid``,
        ordered by timestamp — the programmatic reconstruction of one
        request's story across threads, attempts, and failovers.

        An event correlates when its args carry ``rid == rid`` (the
        emitters thread the user rid through attempt clones, so a
        retried/hedged request's sibling attempts all land here, each
        distinguished by its ``arid``/``lineage`` args) or when it is a
        flow event with ``id == rid``.  Accepts either the wire form
        (``"p0/7"``) or a bare local request id, normalized through
        :func:`corr_rid` — emitters always record the prefixed form."""
        rid = corr_rid(rid)
        with self._lock:
            events = list(self._events)
        out = [e for e in events
               if e.get("args", {}).get("rid") == rid
               or (e.get("cat") == "request" and e.get("id") == rid)]
        out.sort(key=lambda e: e["ts"])
        return out

    def device_window(self, name: str, seconds: float, steps: int = 1,
                      **args) -> None:
        """Record a window-settled device span ending *now*.

        Called right after a boundary drain/sync: the window's wall time
        is attributed to the synthetic device track, one span per
        window (NOT per step — per-step device times do not exist
        without per-step syncs, and we refuse to add those).
        """
        t1 = time.perf_counter()
        self._record(name, t1 - seconds, seconds, DEVICE_TID,
                     {"steps": steps, **args})

    def _record(self, name: str, t0: float, dur: float, tid: int,
                args: dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            ev = {"name": name, "ph": "X",
                  "ts": (t0 - self._t0) * 1e6, "dur": dur * 1e6,
                  "pid": self._meta["pid"], "tid": tid}
            if args:
                ev["args"] = args
            self._events.append(ev)

    # ---- export -------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        with self._lock:
            events = list(self._events)
        meta = [{"name": "thread_name", "ph": "M", "pid": self._meta["pid"],
                 "tid": DEVICE_TID,
                 "args": {"name": "device (window-settled)"}}]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def save(self, path: str) -> str:
        """Write the trace to ``path`` (gzipped when it ends in .gz)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as f:
            json.dump(self.to_chrome(), f)
        return path


_NULL_CTX = contextlib.nullcontext()


class NullTracer:
    """Disabled tracer: every operation is a near-zero no-op (a shared
    nullcontext for spans), so call sites never branch on 'is tracing
    on' themselves."""

    dropped = 0

    def span(self, name: str, **args):
        return _NULL_CTX

    def instant(self, name: str, **args) -> None:
        pass

    def flow(self, name: str, fid: int, phase: str = "step",
             **args) -> None:
        pass

    def request_timeline(self, rid: int) -> list:
        return []

    def device_window(self, name: str, seconds: float, steps: int = 1,
                      **args) -> None:
        pass

    def __len__(self) -> int:
        return 0

    def to_chrome(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        raise ValueError("tracing is disabled; nothing to save")


NULL_TRACER = NullTracer()
