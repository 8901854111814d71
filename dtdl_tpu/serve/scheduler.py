"""Slot-based continuous batcher over the InferenceEngine.

Orca-style iteration-level scheduling on fixed XLA shapes: the engine's
programs always step all ``n_slots`` arena rows; this module decides
*what occupies the rows*.  A request is admitted into the first free
slot (one bucketed prefill), decodes in lockstep with whatever else is
in flight, and retires the moment its budget is exhausted — freeing the
row for the next queued request **mid-flight**, while the other slots
keep decoding.  Short requests never wait for long ones and the batch
never pads to the longest request; the only granularity is one step.

Dispatch discipline (PR 1, SCALING.md "Async dispatch discipline"): the
loop never reads a device value it just dispatched.  The decode feedback
path — sampled token back in as next input — stays ON DEVICE via the
``last_tokens`` vector, so back-to-back steps pipeline without any
host↔device round-trip.  Host-side bookkeeping uses only what the host
already knows at dispatch time.  Sampled tokens reach the host through a
**lag harvest**: each step's token window enters a bounded queue and is
converted ``harvest_lag`` steps later, when the device has long finished
(the same backpressure shape as metrics.MetricsQueue).  The one
consequence: EOS detection is late by up to ``harvest_lag`` steps, so a
slot decodes up to that many garbage steps past its stop token before
retiring — they are trimmed from the output at harvest.
``harvest_lag=0`` restores sync-every-step EOS exactness at
sync-every-step cost.

**Speculative decoding** rides the same discipline.  A request with
``speculate=k > 0`` gets per-step drafts from a host-side
:class:`~dtdl_tpu.serve.draft.DraftSource` — chosen from *lag-harvested
host state* (the source predicts ``gap + k`` tokens continuing the
harvested truth and the optimistic in-flight ``gap`` is skipped — see
``_dispatch_round``'s draft block), never by syncing the in-flight
step —
and the engine's ``verify`` program scores all candidates in one
parameter sweep, accepting a per-slot prefix ON DEVICE
(serve/sampling.py:accept_resample, lossless).  Consequences the
scheduler absorbs:

* **variable tokens per step** — a verify step emits 1..k+1 tokens per
  slot, known only on device, so pending entries carry a token *window*
  plus per-slot counts; budget and EOS checks run over the harvested
  window (EOS mid-window trims exactly, as in the plain path).
* **retirement on guaranteed progress** — the host can no longer count
  emitted tokens at dispatch; every step guarantees >= 1 token, so a
  slot retires when its guaranteed count reaches its budget (for
  non-speculative slots this is exactly the old dispatched count).
  Accepted tokens beyond the budget are trimmed at harvest.
* **worst-case index tracking** — verify writes a k+1-token window at
  the slot's cache position, so the scheduler tracks each slot's
  worst-case (all-accepted) index and, within k of ``max_seq``, settles
  in-flight steps before dispatching (the only data-dependent syncs, and
  only ever in the last k positions of a sequence).
* **adaptive draft length** — each slot tracks a trailing-acceptance
  EMA and halves/doubles its draft length k accordingly; the step's
  width is the power-of-two bucket of the largest per-slot k, so mixed
  spec/non-spec traffic shares one verify program per bucket
  (non-speculative slots ride along with ``draft_len=0`` and behave
  exactly like a decode step — token-identical, pinned by
  tests/test_spec_decode.py).

**Chunked prefill** (round 19, ``chunk_tokens=N``) makes prompt
processing incremental and schedulable: admission only binds a slot
(and maps its pages), then the prompt enters in per-step chunks of at
most N tokens riding the SAME verify program as ``forced`` rows —
"verify with no acceptance test" — so decode steps, speculative drafts
and prefill chunks share one compiled step and a long admission stops
stalling every in-flight decode by a whole-prompt prefill latency
(``decode_steps_delayed_by_prefill`` is the pre-change counter).  The
final chunk's bonus sample IS the request's first token, from the same
target distribution whole-prompt prefill samples — greedy output is
token-identical either way (tests/test_chunked_prefill.py).  A
``prefill_only`` request (the fleet's disaggregation, round 19)
finishes at that first token with a page-granular ``kv_handoff``
payload; a ``kv_inject`` request adopts one and decodes as if it had
prefilled locally.

**Paged KV** (an engine built with ``page_size > 0``) moves the
admission currency from slots to PAGES.  The scheduler owns the
host-side :class:`~dtdl_tpu.serve.paged.PageAllocator` (free list +
chained-hash prefix cache over full prompt pages): admission maps the
longest cached prompt-prefix read-only (shared, refcounted) and
prefills only the suffix through its (smaller) bucket — the TTFT win —
waiting in FIFO order when the pool cannot map the prompt yet; decode
growth allocates pages from the same worst-case ``pos_hi`` arithmetic
the overflow settling uses (no device reads, no new programs — the
fresh page table rides into the next dispatch as data); retirement
releases pages immediately (cached prefix pages stay warm, evictable
LRU).  A mid-flight slot the pool cannot grow for is shed with the
named :class:`~dtdl_tpu.serve.paged.PagePoolExhaustedError` message
(``requests_shed``) rather than stalling the batch.  Token streams are
identical to the dense arena's, pinned by tests/test_paged_kv.py.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Optional, Sequence

import jax
import numpy as np

from dtdl_tpu.obs.observer import NULL_OBSERVER
from dtdl_tpu.obs.trace import corr_rid
from dtdl_tpu.serve.draft import DraftSource, NGramDraft
from dtdl_tpu.serve.engine import InferenceEngine, PromptTooLongError
from dtdl_tpu.serve.metrics import ERROR_KINDS, ServeMetrics
from dtdl_tpu.serve.paged import (GARBAGE_PAGE, DiskPageStore,
                                  HostPageStore, PageAllocator,
                                  PagePoolExhaustedError, payload_nbytes)
from dtdl_tpu.serve.sampling import GREEDY, SampleParams
from dtdl_tpu.serve.tenant.lora import AdapterBankFullError

_ids = itertools.count()


@dataclasses.dataclass
class Request:
    """One generation request plus its lifecycle record.

    ``tokens`` fills with the generated tokens (eos included, post-eos
    trimmed) as they harvest; ``done`` flips when the last one lands.
    ``speculate`` is the request's maximum draft length (0 = plain
    decode); ``error`` is set instead of raising when the scheduler
    rejects the request at submit (e.g. prompt longer than the engine's
    largest prefill bucket, admission queue full, scheduler shut down),
    expires it past its deadline, or fails it during engine containment
    — one bad request never crashes a run with others in flight.
    ``error`` always starts with the terminal kind — ``rejected:`` /
    ``expired:`` / ``failed:`` / ``aborted:`` / ``shed:`` — so callers
    (the fleet Router above all) can branch on the flavor without
    parsing prose.

    Deadlines come in two spellings: ``deadline_s`` is a wall-clock
    budget *from this scheduler's submit* (the PR 5 semantics), while
    ``deadline_at`` is an **absolute** ``time.perf_counter()`` instant.
    A front queue (the fleet Router) sets ``deadline_at`` once at *its*
    intake, so time spent queued ahead of the scheduler counts against
    the budget — without it a request could wait out its whole
    allowance in a router queue and still get a fresh one at the
    engine.  When only ``deadline_s`` is given, ``submit`` derives
    ``deadline_at = t_submit + deadline_s``.

    ``origin_rid``/``lineage`` are the trace-correlation fields (round
    16): a fleet Router stamps each replica-local attempt clone with
    the USER request's rid and how the attempt came to be (``primary``
    / ``retry:N`` after N burned retries / ``requeue`` for a free
    backpressure re-dispatch / ``hedge`` / ``migrate`` for the decode
    half of a disaggregated flight), so every request-scoped
    trace event the
    scheduler emits carries the user rid and
    ``Tracer.request_timeline(rid)`` can reassemble a hedged,
    failed-over request across threads.  Standalone requests leave them
    at the defaults (their own rid is the correlation id).

    **Disaggregation fields (round 19).** ``prefill_only`` asks this
    scheduler for the PREFILL half only: the request finishes the
    moment its first token harvests, with ``kv_handoff`` set to the
    host-side page payload (prompt K/V pages + first token) a decode
    replica's ``kv_inject`` admission adopts — the fleet Router is the
    carrier (dtdl_tpu/serve/fleet.py).  Both require a paged engine;
    standalone callers normally leave them alone.
    """
    prompt: Sequence[int]
    max_new_tokens: int
    sampling: SampleParams = GREEDY
    eos_id: Optional[int] = None
    speculate: int = 0
    deadline_s: Optional[float] = None
    deadline_at: Optional[float] = None
    origin_rid: Optional[int] = None
    lineage: str = "primary"
    prefill_only: bool = False
    kv_inject: Optional[dict] = dataclasses.field(default=None,
                                                  repr=False)
    kv_handoff: Optional[dict] = dataclasses.field(default=None,
                                                   repr=False)
    # multi-tenant fields (round 22, dtdl_tpu/serve/tenant/):
    # ``adapter`` names a LoRA checkpoint path the engine's adapter
    # bank hot-loads (None = base weights); ``grammar`` is a compiled
    # tenant.grammar.TokenDFA constraining every emitted token (needs
    # ``eos_id``: the DFA legalizes EOS only in accepting states);
    # ``stream`` is a tenant.stream.TokenStream delivering tokens
    # incrementally at each lag-harvest (prefix-stable under fleet
    # retries/hedging — only the winning attempt streams).
    adapter: Optional[str] = None
    grammar: Any = dataclasses.field(default=None, repr=False)
    stream: Any = dataclasses.field(default=None, repr=False)
    rid: int = dataclasses.field(default_factory=lambda: next(_ids))
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None
    # wall-clock lifecycle (host side; first/done are harvest times, i.e.
    # when the host could actually observe the token)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    admit_step: int = -1
    # internal: tokens guaranteed emitted by dispatched steps (>= 1 per
    # step; exact for non-speculative slots) / slot retired / the
    # grammar automaton's state over the HARVESTED tokens (lives on the
    # request, not the slot: budget-retired slots keep harvesting
    # windows after the row is reassigned)
    _guaranteed: int = dataclasses.field(default=0, repr=False)
    _retired: bool = dataclasses.field(default=False, repr=False)
    _gq: int = dataclasses.field(default=0, repr=False)

    def __post_init__(self):
        if self.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{self.max_new_tokens}")
        if self.speculate < 0:
            raise ValueError(f"speculate must be >= 0, got "
                             f"{self.speculate}")

    def __repr__(self):
        # the dataclass default would dump the whole prompt and token
        # list — useless in a log line and unreadable for the fleet's
        # per-attempt diagnostics.  One compact line: identity, sizes,
        # lifecycle state, and the error if any.
        state = ("pending" if not self.done
                 else "error" if self.error else "done")
        err = f", error={self.error!r}" if self.error else ""
        return (f"Request(rid={self.rid}, prompt_len={len(self.prompt)}, "
                f"max_new_tokens={self.max_new_tokens}, "
                f"tokens={len(self.tokens)}, {state}{err})")


class _SlotState:
    """Host-side per-slot tracking while a request occupies the row.

    ``pos`` is the slot's cache index as of the last *harvested* step
    (exact); ``inflight`` holds each dispatched-but-unharvested step's
    draft length, so ``pos_hi`` bounds the device index from above (the
    all-accepted worst case) and ``gap`` is the optimistic number of
    tokens the device is ahead of the harvested truth — the draft
    source predicts *across* that gap fresh every step, so a
    misprediction self-heals at the next harvest instead of poisoning
    later drafts.  ``k_cur`` is the adaptive draft length, steered by a
    trailing-acceptance EMA.

    ``fill_next``/``fill_end`` are the CHUNKED-PREFILL cursor (round
    19): while ``fill_next < fill_end`` the slot is still absorbing its
    prompt in per-step chunks (``fill_next`` = the next prompt offset
    to write, advanced at chunk dispatch — host truth, always equal to
    ``pos_hi``) and never decodes, drafts, or emits.  Whole-prompt
    admission leaves them equal (nothing to fill).
    """

    __slots__ = ("rid", "pos", "k_cur", "k_max", "acc_ema", "inflight",
                 "fill_next", "fill_end", "fill_toks")

    def __init__(self, rid: int, pos: int, k_max: int,
                 fill_end: Optional[int] = None):
        self.rid = rid
        self.pos = pos
        self.k_max = k_max
        # start at 2 and let the acceptance EMA steer: doubles under
        # sustained acceptance (>0.8) up to the request's ``speculate``,
        # halves under <0.5 — so a weak draft source costs at most a few
        # over-drafted steps before settling at k=1
        self.k_cur = max(1, min(2, k_max))
        self.acc_ema = 1.0          # optimistic until measured
        self.inflight: deque = deque()
        self.fill_next = pos
        self.fill_end = pos if fill_end is None else fill_end
        # the prompt as one int32 array, materialized ONCE at chunked
        # admission: chunk building slices it per step — re-listing the
        # whole prompt per chunk would cost O(len^2/chunk) host work on
        # exactly the long-prompt path chunking exists for
        self.fill_toks = None

    @property
    def prefilling(self) -> bool:
        """Still absorbing prompt chunks — excluded from decode/draft."""
        return self.fill_next < self.fill_end

    @property
    def pos_hi(self) -> int:
        """Worst-case (all-accepted) device index — the overflow bound."""
        return self.pos + sum(dl + 1 for dl, _ in self.inflight)

    @property
    def gap_est(self) -> int:
        """EXPECTED tokens of the request's OUTPUT stream the device is
        ahead of harvested truth: one guaranteed per in-flight
        decode/verify step plus acceptance-EMA-weighted drafts.  At
        high acceptance this is the all-accepted count (aligned
        drafting, the payoff regime); at low acceptance it decays to
        one-per-step, which is what the device is actually doing —
        either way the skip stays close to the true offset.  In-flight
        PREFILL CHUNKS advance the cache index, never the output
        stream: an intermediate chunk contributes 0 and the final
        chunk exactly its bonus token — counting chunk widths here
        would make the first post-prefill draft windows skip ~a whole
        chunk of the proposal and reject guaranteed."""
        a = min(1.0, max(0.0, self.acc_ema))
        out = 0
        for dl, kind in self.inflight:
            if kind == 1:
                continue               # intermediate chunk: no output
            out += 1 if kind == 2 else 1 + int(round(dl * a))
        return out

    def dispatched(self, draft_len: int, kind: int = 0) -> None:
        self.inflight.append((draft_len, kind))

    def settle(self, draft_len: int, n_emitted: int) -> None:
        """One in-flight step harvested: exact index, acceptance EMA,
        and the multiplicative k adaptation (halve under ~50%% trailing
        acceptance, double — up to the request's ``speculate`` — above
        ~80%%)."""
        if self.inflight:
            self.inflight.popleft()
        self.pos += n_emitted
        if draft_len > 0:
            rate = (n_emitted - 1) / draft_len
            self.acc_ema = 0.5 * self.acc_ema + 0.5 * rate
            if self.acc_ema < 0.5:
                self.k_cur = max(1, self.k_cur // 2)
            elif self.acc_ema > 0.8:
                self.k_cur = min(max(1, self.k_cur * 2), self.k_max)


class Scheduler:
    """Continuous batcher (see module docstring).

    ``submit`` enqueues (or rejects — see :class:`Request` ``error``);
    ``step`` runs one admit+draft+decode/verify round; ``run`` drives
    until everything submitted has finished and returns the finished
    requests in completion order.  ``draft`` is the
    :class:`~dtdl_tpu.serve.draft.DraftSource` used for requests with
    ``speculate > 0`` (default: device-free n-gram prompt lookup).
    """

    def __init__(self, engine: InferenceEngine, seed: int = 0,
                 harvest_lag: int = 4, metrics: ServeMetrics = None,
                 observer=None, draft: Optional[DraftSource] = None,
                 max_queue: Optional[int] = None,
                 prefix_cache: bool = True, exporter=None,
                 chunk_tokens: Optional[int] = None,
                 spill_host_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 spill_disk_bytes: Optional[int] = None):
        if harvest_lag < 0:
            raise ValueError(f"harvest_lag must be >= 0, got "
                             f"{harvest_lag}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if chunk_tokens is not None and chunk_tokens < 1:
            raise ValueError(f"chunk_tokens must be >= 1, got "
                             f"{chunk_tokens}")
        # obs facade: thread-safe spans (admit/draft/dispatch/verify/
        # harvest) + the engine's recompile sentinel; defaults to no-ops
        self.observer = observer or NULL_OBSERVER
        if observer is not None and engine.observer is None:
            engine.observer = observer   # sentinel on the engine's jits
        # continuous metrics export (dtdl_tpu/obs/export.py): sampled at
        # the boundaries this loop already settles at — step's harvest
        # and drain() — never per token; the exporter throttles itself
        self.exporter = exporter
        self.engine = engine
        self.draft = draft if draft is not None else NGramDraft()
        draft_model = getattr(self.draft, "model", None)
        if draft_model is not None and \
                draft_model.vocab_size != engine.model.vocab_size:
            raise ValueError(
                f"draft model vocab ({draft_model.vocab_size}) must match "
                f"the served model's ({engine.model.vocab_size})")
        self.arena = engine.init_arena()
        self.last_tokens = engine.init_last_tokens()
        self.queue: deque[Request] = deque()
        self.slots: list[Optional[Request]] = [None] * engine.n_slots
        self.harvest_lag = harvest_lag
        self.metrics = metrics or ServeMetrics(n_slots=engine.n_slots)
        if exporter is not None:
            # this scheduler's window-delta feed; callers stack further
            # sources (goodput totals, guard counters) on the same
            # exporter before or after construction
            exporter.add_source("", self.metrics.window)
        self.finished: list[Request] = []
        self._reqs: dict[int, Request] = {}
        self._active = np.zeros(engine.n_slots, bool)
        self._state: list[Optional[_SlotState]] = [None] * engine.n_slots
        self._temp = np.zeros(engine.n_slots, np.float32)
        self._topk = np.zeros(engine.n_slots, np.int32)
        self._topp = np.ones(engine.n_slots, np.float32)
        self._key = jax.random.PRNGKey(seed)
        # lag harvest: (token window [B] or [B, k+1], per-slot counts or
        # None (=1 each), ((slot, rid, draft_len), ...))
        self._pending: deque[tuple[Any, Any, tuple]] = deque()
        self.step_count = 0
        # containment state: bounded admission + graceful shutdown +
        # the blast radius of an engine failure (see step()/shutdown())
        self.max_queue = max_queue
        self._closed = False
        self._containing = False
        self.last_engine_error: Optional[str] = None
        # watchdog early-out: stays False until a deadline-carrying
        # request is submitted, so the per-step queue/slot scan is free
        # for the (default) deadline-less workload
        self._deadlines_seen = False
        # paged KV arena (dtdl_tpu/serve/paged.py): host-side page
        # allocator + prefix cache, the per-slot page tables the
        # compiled programs consume as data, and the per-slot page
        # lists for release at retirement.  Admission is gated on FREE
        # PAGES, not free slots: a free slot whose prompt cannot be
        # mapped waits in the queue (FIFO backpressure) until
        # retirements free pages or the prefix cache eats the need.
        self.pages: Optional[PageAllocator] = None
        # hierarchical KV cache (round 23): the host-DRAM spill tier
        # (plus optional disk tier) behind the HBM prefix cache, and the
        # bounded receipt queue the fleet Router drains to keep its
        # prefix directory fresh — ("add", hash) when this replica
        # publishes a prefix page in ANY tier, ("drop", hash) when the
        # last tier forgets it, ("reset", 0) on containment.  A dropped
        # receipt (deque overflow) only makes the directory stale, and a
        # stale directory entry only costs a recompute.
        self.spill: Optional[HostPageStore] = None
        self.kv_receipts: deque = deque(maxlen=65536)
        if engine.paged:
            self.pages = PageAllocator(engine.n_pages, engine.page_size,
                                       prefix_cache=prefix_cache)
            self._ptab = np.full((engine.n_slots, engine.n_ptab),
                                 GARBAGE_PAGE, np.int32)
            self._slot_pages: list[list[int]] = \
                [[] for _ in range(engine.n_slots)]
            if spill_host_bytes is not None or spill_dir is not None:
                if not prefix_cache:
                    raise ValueError("spill tiers require "
                                     "prefix_cache=True (spilled pages "
                                     "are keyed by chain hash)")
                disk = (DiskPageStore(spill_dir, spill_disk_bytes)
                        if spill_dir is not None else None)
                self.spill = HostPageStore(
                    spill_host_bytes if spill_host_bytes is not None
                    else 0,
                    disk=disk,
                    on_drop=lambda h: self.kv_receipts.append(("drop", h)))
                self.pages.record_evictions = True
        elif spill_host_bytes is not None or spill_dir is not None:
            raise ValueError("spill_host_bytes/spill_dir require a paged "
                             "engine with prefix_cache=True")
        # chunked prefill (round 19, Sarathi-style): prompt processing
        # split into <= chunk_tokens-per-step windows riding the verify
        # program family, so a long admission no longer stalls every
        # in-flight decode by a whole-prompt prefill latency.  None =
        # the PR 2 whole-prompt behavior, token-identical under greedy
        # (tests/test_chunked_prefill.py pins both ways).
        self.chunk_tokens = chunk_tokens
        # paged+chunked: prefix-hash registration is deferred until the
        # prompt's pages are fully written (the final chunk's dispatch)
        self._slot_hashes: list = [None] * engine.n_slots
        # multi-tenant LoRA (round 22): per-slot adapter-bank row ids,
        # the [B] vector every decode/verify step consumes as DATA
        # (row 0 = the all-zeros base adapter).  The scheduler owns the
        # refcount lifecycle: acquire at admission, release at retire.
        self._aids = np.zeros(engine.n_slots, np.int32)
        if engine.adapter_bank is not None \
                and engine.adapter_bank.observer is None:
            engine.adapter_bank.observer = self.observer

    # ---- intake -------------------------------------------------------

    _ERROR_KINDS = ERROR_KINDS

    def _corr(self, req: Request) -> dict:
        """Trace-correlation args for request-scoped events: ``rid`` is
        the USER request id (the fleet Router stamps ``origin_rid`` on
        attempt clones; standalone requests are their own origin),
        ``arid`` the local attempt id — so
        ``Tracer.request_timeline(rid)`` collects every attempt's
        events under the one user rid while ``arid`` tells the sibling
        attempts apart.  Both land in the wire form (``corr_rid``:
        ``f"{proc_tag}/{n}"``, round 17) so multi-host traces merge
        without id collisions."""
        rid = req.origin_rid if req.origin_rid is not None else req.rid
        return {"rid": corr_rid(rid), "arid": corr_rid(req.rid)}

    def _finish_error(self, req: Request, reason: str,
                      metric_hook, kind: str) -> Request:
        """The one terminal-error path: ``req.error`` set to
        ``"<kind>: <reason>"`` (kind ∈ rejected / expired / failed /
        aborted / shed — the machine-checkable flavor a caller branches
        on), request finished, the given metrics hook (on_reject /
        on_expire / on_failure / on_abort / on_shed) counts it — every
        containment branch funnels through here so retirement
        bookkeeping and message format cannot drift."""
        assert kind in self._ERROR_KINDS, kind
        req.error = f"{kind}: {reason}"
        req.done = True
        req.t_done = time.perf_counter()
        self.finished.append(req)
        self._stream_terminal(req)
        metric_hook(req)
        if req.origin_rid is None and req.admit_step >= 0:
            # a STANDALONE request that was admitted started a flow
            # chain at admission — every terminal funnels through here,
            # so close it (never-admitted requests started none, and
            # fleet attempts' chains are closed by the Router's
            # request_done, which owns the user-level outcome)
            self.observer.flow("req", corr_rid(req.rid), "end")
        return req

    def _reject(self, req: Request, reason: str) -> Request:
        """Terminal submit-time rejection: ``req.error`` set, counted,
        run unharmed — the named-error-instead-of-crash path shared by
        oversized prompts, a full admission queue, and shutdown."""
        self._reqs[req.rid] = req
        return self._finish_error(req, reason, self.metrics.on_reject,
                                  "rejected")

    def _stream_terminal(self, req: Request) -> None:
        """Close out a streaming request's TokenStream at its terminal.

        Ownership protocol (tenant/stream.py): a STANDALONE request
        (``origin_rid`` is None) owns the user-facing stream outright,
        so its terminal reconciles and closes it — success delivers any
        suffix the lag harvest had not offered yet, an error closes
        without delivering.  A fleet ATTEMPT only *releases* its claim,
        and only on an error terminal, so a retry/hedge successor can
        take over and the stream stays prefix-stable — the Router's
        ``_finish_user`` owns the user-level close."""
        if req.stream is None:
            return
        if req.origin_rid is None:
            req.stream.finish(req.tokens, req.error)
        elif req.error is not None:
            req.stream.drop(req.rid)

    def _acquire_adapter(self, req: Request) -> Optional[int]:
        """Pin ``req``'s LoRA adapter in the engine's bank at admission
        (hot-loading it through the manifest-checked checkpoint path
        when cold).  Returns the bank row id (0 = base weights), or
        None after error-finishing the request with a named reason: a
        bank with every row pinned by live requests **sheds** with the
        :class:`AdapterBankFullError` message (a capacity signal,
        exactly the page-pool discipline), a corrupt or unreadable
        adapter checkpoint **fails** — neither crashes the loop."""
        if req.adapter is None:
            return 0
        try:
            return self.engine.adapter_bank.acquire(req.adapter)
        except AdapterBankFullError as e:
            self.queue.remove(req)
            self._finish_error(req, str(e), self.metrics.on_shed, "shed")
        except Exception as e:
            self.queue.remove(req)
            self._finish_error(
                req, f"adapter {req.adapter!r} failed to load: {e}",
                self.metrics.on_failure, "failed")
        return None

    def submit(self, req: Request) -> Request:
        """Enqueue ``req``; a request the scheduler cannot serve comes
        back *rejected* (``req.error`` set, ``req.done`` True, counted in
        ``requests_rejected``) instead of raising — one bad request must
        not crash a run with other requests in flight.  Rejection
        reasons: prompt past the largest prefill bucket, admission queue
        at ``max_queue`` (bounded intake: a traffic spike sheds load
        here, with a named reason, instead of growing an unbounded host
        queue), or a shut-down scheduler."""
        prompt_len = len(req.prompt)
        if prompt_len < 1:
            raise ValueError("empty prompt")
        req.t_submit = time.perf_counter()
        if self._closed:
            return self._reject(req, "scheduler is shut down")
        if self._containing:
            # a thread-hosted scheduler (the fleet Replica) can receive
            # a submit while _contain is mid-flight on the worker —
            # admitting into an arena being re-initialized would race;
            # the same named-reason rejection path applies (retryable)
            return self._reject(
                req, "engine containment in progress; retry shortly")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return self._reject(
                req, f"admission queue full ({self.max_queue} waiting); "
                     f"retry later")
        if req.adapter is not None and self.engine.adapter_bank is None:
            return self._reject(
                req, "adapter requests need an engine built with an "
                     "adapter bank (lora_rank/lora_adapters)")
        if req.grammar is not None:
            # the DFA legalizes EOS only in accepting states, which is
            # how a constrained request stops on a complete structure —
            # without an eos_id the constraint could never terminate
            if req.eos_id is None:
                return self._reject(
                    req, "grammar-constrained requests need eos_id (the "
                         "automaton legalizes EOS in accepting states)")
            if req.grammar.eos_id != req.eos_id:
                return self._reject(
                    req, f"grammar was compiled for eos_id="
                         f"{req.grammar.eos_id} but the request has "
                         f"eos_id={req.eos_id}")
            if req.grammar.allow.shape[1] != self.engine.model.vocab_size:
                return self._reject(
                    req, f"grammar was compiled over a vocab of "
                         f"{req.grammar.allow.shape[1]} tokens; the "
                         f"engine serves "
                         f"{self.engine.model.vocab_size}")
        if req.prefill_only and req.kv_inject is not None:
            raise ValueError("prefill_only and kv_inject are mutually "
                             "exclusive (one request is one half of a "
                             "disaggregated flight)")
        if (req.prefill_only or req.kv_inject is not None) \
                and self.pages is None:
            return self._reject(
                req, "prefill/decode disaggregation needs a paged "
                     "engine (page_size > 0): the KV handoff is "
                     "page-granular")
        if req.kv_inject is not None:
            # the decode half of a migrated flight: no prefill ever
            # runs, so the bucket check is irrelevant — validate the
            # payload geometry and that decoding has room instead
            pg = self.engine.page_size
            n_pg = int(req.kv_inject.get("n_pages", 0))
            if n_pg != -(-prompt_len // pg):
                return self._reject(
                    req, f"kv_inject payload carries {n_pg} pages but "
                         f"the prompt needs {-(-prompt_len // pg)} "
                         f"(page_size={pg})")
            if prompt_len >= self.engine.max_seq:
                return self._reject(
                    req, f"adopted prompt of {prompt_len} tokens "
                         f"leaves no room to decode "
                         f"(max_seq={self.engine.max_seq})")
            need = (prompt_len + 1 + pg - 1) // pg
            if need > self.pages.capacity:
                return self._reject(
                    req, f"page pool exhausted: adopted prompt needs "
                         f"{need} pages (page_size={pg}) but the pool "
                         f"has only {self.pages.capacity}")
            if req.deadline_at is not None or req.deadline_s is not None:
                self._deadlines_seen = True
            if req.deadline_at is None and req.deadline_s is not None:
                req.deadline_at = req.t_submit + req.deadline_s
            self._reqs[req.rid] = req
            self.queue.append(req)
            self.metrics.on_submit(req)
            return req
        try:
            self.engine.bucket_for(prompt_len)
        except PromptTooLongError as e:
            return self._reject(req, str(e))
        if self.pages is not None:
            # never-fits guard: a prompt whose pages (plus the first
            # generated token's) exceed the whole pool would wait at
            # admission forever — shed it NOW with the diagnosis
            pg = self.engine.page_size
            need = (prompt_len + 1 + pg - 1) // pg
            if need > self.pages.capacity:
                return self._reject(
                    req, f"page pool exhausted: prompt needs {need} "
                         f"pages (page_size={pg}) but the pool has "
                         f"only {self.pages.capacity}")
        if req.deadline_at is None and req.deadline_s is not None:
            # the PR 5 relative spelling: budget starts at THIS submit
            req.deadline_at = req.t_submit + req.deadline_s
        if req.deadline_at is not None:
            self._deadlines_seen = True
        self._reqs[req.rid] = req
        self.queue.append(req)
        self.metrics.on_submit(req)
        return req

    # ---- slot lifecycle ----------------------------------------------

    def _budget(self, req: Request) -> int:
        # the k-th decode step writes K/V at position len(prompt)+k-1,
        # which must stay < max_seq; prefill contributes token 1 for free
        return min(req.max_new_tokens,
                   self.engine.max_seq - len(req.prompt) + 1)

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _retire(self, slot: int):
        req = self.slots[slot]
        req._retired = True
        self.slots[slot] = None
        self._active[slot] = False
        # reset the slot's sampling knobs to greedy: a retired sampled
        # request must not keep jnp.all(greedy) False forever and
        # disable the all-greedy verify fast path for later traffic
        # (sampling params are data — no recompile)
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._topp[slot] = 1.0
        # drop this slot's claim on its LoRA bank row: refcount 0 makes
        # the row LRU-evictable for the next cold adapter, while the
        # weights stay resident for a warm re-acquire (row 0, the base
        # adapter, is never refcounted — release(0) is a no-op)
        if self._aids[slot]:
            self.engine.adapter_bank.release(int(self._aids[slot]))
            self._aids[slot] = 0
        if self.pages is not None:
            # release the slot's pages (cached prefix pages become
            # evictable, private pages free immediately) and point the
            # stale table row at the garbage page — any still-in-flight
            # step for this slot was dispatched with its own table
            # snapshot, and the single device stream orders it before
            # whatever prefill reuses the pages (the same
            # overwritten-after-retire discipline as the dense arena)
            for p in self._slot_pages[slot]:
                self.pages.release(p)
            self._slot_pages[slot] = []
            self._ptab[slot] = GARBAGE_PAGE
        # a request retired mid-chunked-prefill (expire/cancel/shed)
        # must not leak its deferred prefix-hash registration to the
        # slot's next occupant — its partially-written pages were just
        # released above, exactly the satellite-bugfix path
        self._slot_hashes[slot] = None

    def _expire(self):
        """Deadline watchdog: retire any request past its wall-clock
        budget with ``req.error`` set — queued or in a slot.  Freeing a
        slot never touches the KV arena (the row is inactive until the
        next prefill overwrites it, the same discipline as retirement),
        and any in-flight harvest windows for the request are dropped by
        the existing ``req.done`` skip, so an expired request cannot
        poison later occupants of its row.  The scan costs nothing until
        the first deadline-carrying request is submitted."""
        if not self._deadlines_seen:
            return
        now = time.perf_counter()

        def expired(req):
            # deadline_at is the single source of truth (submit derives
            # it from deadline_s) — absolute, so front-queue time spent
            # before this scheduler's submit counts against the budget
            return req.deadline_at is not None and now >= req.deadline_at

        def budget(req):
            return (f"{req.deadline_s}s" if req.deadline_s is not None
                    else f"(absolute, {req.deadline_at - req.t_submit:+.3f}"
                         f"s from submit)")

        for req in [r for r in self.queue if expired(r)]:
            self.queue.remove(req)
            self._finish_error(
                req, f"deadline {budget(req)} exceeded before "
                     f"admission", self.metrics.on_expire, "expired")
            self.observer.event("request_expired", queued=1,
                                **self._corr(req))
        for slot, req in enumerate(self.slots):
            # every OCCUPIED slot is expirable — including a parked
            # prefill_only slot (active False while awaiting its
            # first-token harvest): an expired prefill half must not
            # go on to pay the extraction sync and migrate a dead
            # request
            if req is None or not expired(req):
                continue
            self._finish_error(
                req, f"deadline {budget(req)} exceeded after "
                     f"{len(req.tokens)} tokens", self.metrics.on_expire,
                "expired")
            self.observer.event("request_expired", slot=slot,
                                **self._corr(req))
            self._retire(slot)

    # ---- router-facing hooks (dtdl_tpu/serve/fleet.py) ----------------

    @property
    def load(self) -> int:
        """Host-side occupancy signal for least-loaded routing: queued
        plus slot-occupying requests (a parked prefill_only slot
        awaiting its handoff harvest still holds the slot).  Plain
        reads under the GIL — safe to sample from another thread
        without stopping the step loop."""
        return len(self.queue) + sum(s is not None for s in self.slots)

    def pending_requests(self) -> list:
        """Every submitted-but-unfinished request (queued, slotted, or
        retired-awaiting-harvest) — the outstanding-work export for a
        fleet/ops layer.  (The shipped Router re-dispatches an evicted
        replica's work from its OWN attempt table — it never trusts a
        possibly-wedged replica's bookkeeping — so this is the
        inspection surface, e.g. for drain monitoring, not the failover
        mechanism.)"""
        return [r for r in self._reqs.values() if not r.done]

    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Best-effort cancellation of one request by id: a queued
        request is removed, an in-slot one retires — both finish with
        ``error = "aborted: cancelled ..."`` and count under
        ``requests_aborted`` (a deliberate abort of an already-submitted
        request, exactly the shutdown-abort semantics, so the PR 5
        accounting invariant holds unchanged).  Returns False when it is
        too late to matter: unknown rid, already finished, or already
        retired on guaranteed budget with its tokens merely awaiting the
        lag harvest (those are computed — the harvest delivers them; a
        caller that must not double-deliver, e.g. the Router's hedge
        loser path, discards the completion instead)."""
        req = self._reqs.get(rid)
        if req is None or req.done:
            return False
        if req in self.queue:
            self.queue.remove(req)
            self._finish_error(
                req, f"cancelled before admission: {reason}",
                self.metrics.on_abort, "aborted")
            self.observer.event("request_cancelled", queued=1,
                                **self._corr(req))
            return True
        for slot, r in enumerate(self.slots):
            if r is req:
                self._finish_error(
                    req, f"cancelled after {len(req.tokens)} tokens: "
                         f"{reason}", self.metrics.on_abort, "aborted")
                self.observer.event("request_cancelled", slot=slot,
                                    **self._corr(req))
                self._retire(slot)
                return True
        return False     # retired-awaiting-harvest: let it finish

    def _contain(self, exc: BaseException):
        """Engine-failure blast radius: the in-flight batch.

        A compiled program failing mid-dispatch leaves the donated arena
        in an unknown state, so everything referencing it is condemned:
        every slotted request retires with ``req.error`` set and the
        arena/last-token state is re-initialized.  Harvest windows
        dispatched BEFORE the failure are intact output buffers from
        completed programs — they are delivered first (best-effort), so
        a request that already retired on guaranteed budget and was only
        waiting on the lag harvest still finishes cleanly rather than
        being orphaned ``done=False``; any such request the harvest
        could not settle is error-finished like the slotted ones.  The
        admission queue survives — the next step admits and serves it
        against the fresh arena."""
        self._containing = True
        try:
            self.last_engine_error = f"{type(exc).__name__}: {exc}"
            self.observer.event("engine_failure",
                                error=self.last_engine_error)
            pending_rids = {rid for _, _, entries in self._pending
                            for _, rid, _, _ in entries}
            try:
                while self._pending:
                    self._harvest_one()
            except Exception:      # device state unusable — drop the rest
                self._pending.clear()
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                self._finish_error(
                    req, f"engine failure: {self.last_engine_error}",
                    self.metrics.on_failure, "failed")
                self._retire(slot)
                self._state[slot] = None
            for rid in pending_rids:  # retired-for-budget but unharvested
                req = self._reqs[rid]
                if not req.done:
                    self._finish_error(
                        req, f"engine failure: {self.last_engine_error}",
                        self.metrics.on_failure, "failed")
            self.arena = self.engine.init_arena()
            self.last_tokens = self.engine.init_last_tokens()
            if self.pages is not None:
                # the re-initialized arena invalidated every page's
                # contents — a stale prefix hit would be silent corruption
                self.pages.reset()
                self._ptab[:] = GARBAGE_PAGE
                self._slot_pages = [[] for _ in range(self.engine.n_slots)]
                # tell the fleet directory every HBM-resident hash this
                # replica advertised is gone (host/disk spill copies
                # survive — they are content-addressed host memory)
                self.kv_receipts.append(("reset", 0))
        finally:
            self._containing = False

    def _admit(self):
        if self._closed:
            return
        for slot in range(self.engine.n_slots):
            if self.slots[slot] is not None or not self.queue:
                continue
            req = self.queue[0]
            if req.kv_inject is not None:
                # the decode half of a disaggregated flight: adopt the
                # migrated pages instead of prefilling (round 19)
                if self._admit_inject(slot, req):
                    continue
                break                  # pool backpressure: FIFO waits
            aid = self._acquire_adapter(req)
            if aid is None:
                continue               # shed/failed with a named error
            chunked = self.chunk_tokens is not None
            suffix, start, row = req.prompt, 0, None
            hits, fresh, hashes, restored = [], [], [], []
            if self.pages is not None:
                # paged admission: gate on FREE PAGES.  Match the
                # longest cached run of full prompt pages (mapped
                # read-only, shared), allocate private pages for the
                # rest, and prefill only the uncached suffix — the
                # prefix-cache TTFT win.  A prompt the pool cannot map
                # right now WAITS (FIFO backpressure; retirements free
                # pages) instead of stealing a slot it cannot fill.
                pg = self.engine.page_size
                prompt = [int(t) for t in req.prompt]
                hits = self.pages.match_prefix(prompt)
                # hashing is O(prompt) host work on the TTFT path —
                # skip it entirely when the cache can never hit
                hashes = (self.pages.page_hashes(prompt)
                          if self.pages.prefix_cache else [])
                if self.spill is not None:
                    # restore-on-miss (round 23): continue the chain
                    # walk into the host/disk spill tiers — every
                    # payload found there is one page of prefill
                    # recompute skipped for a host->HBM copy
                    for i in range(len(hits),
                                   (len(prompt) - 1) // pg):
                        tier = self.spill.holds(hashes[i])
                        payload = (self.spill.get(hashes[i])
                                   if tier is not None else None)
                        if payload is None:
                            if tier == "disk":
                                # held by the manifest but failed its
                                # integrity check: quarantined by the
                                # store, recomputed by us
                                self.metrics.on_spill_quarantine(1)
                            break             # miss: recompute
                        restored.append((payload, tier))

                def resident() -> int:
                    # prompt pages already materialized across ALL
                    # tiers: HBM hits + spill-tier payloads to inject
                    return len(hits) + len(restored)

                def drop_one() -> None:
                    # trim trailing resident pages (restored first —
                    # they sit after the HBM hits on the chain; their
                    # payloads stay warm in the spill store)
                    (restored if restored else hits).pop()
                if chunked:
                    # chunks write EXACT positions (no padded bucket),
                    # so the bucket-overshoot cap does not apply; the
                    # one constraint is never stranding a 1-token final
                    # chunk at position max_seq-1 (a k>=1 verify window
                    # there would clamp backward over cached pages)
                    while resident() \
                            and len(prompt) == self.engine.max_seq \
                            and len(prompt) - resident() * pg < 2:
                        drop_one()
                else:
                    # the suffix's PADDED bucket must also fit max_seq —
                    # the kernel clamps an overshooting window backward,
                    # which would scatter over the cached pages
                    # themselves.  Dropping trailing resident pages
                    # grows the suffix (monotonic: zero resident == the
                    # submit-checked full prompt), so this always
                    # terminates on a valid configuration.
                    while resident() and (resident() * pg
                                          + self.engine.bucket_for(
                                              len(prompt)
                                              - resident() * pg)
                                          > self.engine.max_seq):
                        drop_one()
                start = resident() * pg
                n_prompt_pages = -(-len(prompt) // pg)
                need = n_prompt_pages - len(hits)
                # pinning an evictable (refcount-0) hit consumes one
                # available page too — count both demands
                evictable_hits = sum(
                    1 for p in hits if self.pages.refcount(p) == 0)
                if need + evictable_hits > self.pages.available:
                    if aid:   # un-pin the adapter row while FIFO waits:
                        self.engine.adapter_bank.release(aid)
                    break     # re-acquired (warm) when pages free up
                for p in hits:          # pin BEFORE alloc can evict them
                    self.pages.acquire(p)
                fresh = [self.pages.alloc() for _ in range(need)]
                # the alloc burst above may have evicted cached pages:
                # extract their payloads to the spill store NOW, before
                # the inject/prefill dispatches below rewrite them
                self._spill_evicted()
                row = np.full(self.engine.n_ptab, GARBAGE_PAGE, np.int32)
                row[:len(hits)] = hits
                row[len(hits):n_prompt_pages] = fresh
                suffix = prompt[start:]
            self.queue.popleft()
            sp = req.sampling
            corr = self._corr(req)
            if restored:
                # restore-on-miss, entry half: the spilled payloads
                # re-enter the arena through the SAME compiled scatter
                # as the PR 14 handoff (fresh pages fresh[:n_res];
                # dispatch-only — the suffix prefill below is ordered
                # after it on the device stream, and its index/last
                # seeding is overwritten by that prefill)
                t0 = time.perf_counter()
                payloads = [p for p, _ in restored]
                data = (payloads[0] if len(payloads) == 1
                        else jax.tree.map(
                            lambda *xs: np.concatenate(xs, axis=0),
                            *payloads))
                try:
                    self.arena, self.last_tokens = \
                        self.engine.inject_pages(
                            self.arena, self.last_tokens, data,
                            fresh[:len(restored)], slot, start, 0)
                except Exception as e:
                    self._contain(e)
                    self._finish_error(
                        req, f"engine failure: {self.last_engine_error}",
                        self.metrics.on_failure, "failed")
                    if aid:   # not slotted yet — _contain missed it
                        self.engine.adapter_bank.release(aid)
                    return
                dt = time.perf_counter() - t0
                nbytes = sum(payload_nbytes(p) for p in payloads)
                self.metrics.on_restore(
                    len(restored), nbytes, dt,
                    host_hits=sum(1 for _, t in restored if t == "host"),
                    disk_hits=sum(1 for _, t in restored if t == "disk"))
                self.observer.event(
                    "page_restored", slot=slot, pages=len(restored),
                    nbytes=nbytes, cached=len(hits) * pg, **corr)
            if not chunked:
                # whole-prompt prefill: one blocking compiled call —
                # every in-flight decode waits a full prefill latency
                # behind it (the interference the chunked path removes;
                # the counter is the before/after receipt)
                self.metrics.on_prefill_block(int(self._active.sum()))
                # grammar: the prefill's bonus sample IS the request's
                # first OUTPUT token, so it draws under the automaton's
                # start-state mask (the prompt itself never advances
                # the DFA — grammars constrain output only)
                g0 = (req.grammar.mask(req.grammar.start)[None, :]
                      if req.grammar is not None else None)
                try:
                    with self.observer.span("prefill", slot=slot,
                                            suffix_len=len(suffix),
                                            cached=start, **corr):
                        self.arena, self.last_tokens, _ = \
                            self.engine.prefill(
                                self.arena, self.last_tokens, slot,
                                suffix, sp, self._next_key(),
                                page_row=row, start=start,
                                adapter_id=(aid if self.engine.adapter_bank
                                            is not None else None),
                                allowed=g0)
                except Exception as e:
                    # the arena was donated into the failing program:
                    # condemn the in-flight batch (and this request),
                    # keep the queue
                    self._contain(e)
                    self._finish_error(
                        req, f"engine failure: {self.last_engine_error}",
                        self.metrics.on_failure, "failed")
                    if aid:   # not slotted yet — _contain missed it
                        self.engine.adapter_bank.release(aid)
                    return
            if self.pages is not None:
                self._ptab[slot] = row
                self._slot_pages[slot] = list(hits) + list(fresh)
                n_res = len(restored)
                # restored pages' contents are complete at the inject
                # dispatch above: publish them back into the HBM cache
                # now, whichever prefill path follows
                for i in range(len(hits), len(hits) + n_res):
                    self.pages.register(hashes[i], int(row[i]))
                    self.kv_receipts.append(("add", hashes[i]))
                if chunked:
                    # registration of the SUFFIX pages waits for the
                    # final chunk: only then are they fully written
                    self._slot_hashes[slot] = (hashes,
                                               len(hits) + n_res)
                else:
                    # publish the freshly-computed FULL prompt pages
                    # under their chain hashes — the next identical
                    # prefix hits (deterministic model: same tokens at
                    # same positions => identical K/V, so
                    # first-writer-wins is sound)
                    for i in range(len(hits) + n_res, len(hashes)):
                        self.pages.register(hashes[i], int(row[i]))
                        self.kv_receipts.append(("add", hashes[i]))
                # resident prefix pages — HBM hits AND spill restores —
                # all count as hits: their tokens skipped recompute
                self.metrics.on_prefix(len(hits) + n_res, len(hashes),
                                       start)
            self.slots[slot] = req
            self._active[slot] = True
            self._aids[slot] = aid
            if req.grammar is not None:
                req._gq = req.grammar.start
            self._state[slot] = _SlotState(
                req.rid, start if chunked else len(req.prompt),
                req.speculate,
                fill_end=len(req.prompt) if chunked else None)
            if chunked:
                # audit: ok[host-sync-asarray] chunked-prefill queue of the caller's host prompt list
                self._state[slot].fill_toks = np.asarray(req.prompt,
                                                         np.int32)
            self._temp[slot] = sp.temperature
            self._topk[slot] = sp.top_k
            self._topp[slot] = sp.top_p
            req.t_admit = time.perf_counter()
            req.admit_step = self.step_count
            # correlated admission marker on this worker's track: the
            # queue-wait is readable as (this ts - the submit/dispatch
            # event's), and the flow arrow joins the attempt to its
            # user request's chain (standalone requests START the flow
            # here; fleet attempts continue the router's)
            self.observer.event("request_admitted", slot=slot,
                                step=self.step_count,
                                prompt_len=len(req.prompt),
                                cached=start, lineage=req.lineage,
                                **corr)
            self.observer.flow(
                "req", corr["rid"],
                "step" if req.origin_rid is not None else "start")
            # prefill_tokens counts COMPUTED tokens: a prefix hit's
            # skipped tokens land in prefill_tokens_saved instead
            self.metrics.on_admit(req, slot, len(suffix))
            if chunked:
                # no token guaranteed yet: the first one is the final
                # chunk's bonus sample (_dispatch_round)
                continue
            req._guaranteed = 1
            self._state[slot].dispatched(0)
            self._pending.append(
                (self.last_tokens, None, ((slot, req.rid, 0, 0),)))
            if req._guaranteed >= self._budget(req):
                self._retire(slot)
            elif req.prefill_only:
                # prefill-role replica: park the slot (no decode steps)
                # until the first token harvests and the page payload
                # is extracted (_harvest_one -> _handoff_out)
                self._active[slot] = False

    def _admit_inject(self, slot: int, req: Request) -> bool:
        """Admission of a migrated (``kv_inject``) request: allocate
        fresh pages, write the extracted prompt K/V into the pool, seed
        the slot's cache index and last-token entry — after which the
        slot decodes through the ordinary programs exactly as if this
        scheduler had prefilled it (greedy token identity is the
        disaggregation oracle).  Returns False when the pool cannot map
        the payload yet (FIFO backpressure, like prefill admission)."""
        payload = req.kv_inject
        n_pg = int(payload["n_pages"])
        if n_pg > self.pages.available:
            return False
        aid = self._acquire_adapter(req)
        if aid is None:
            return True            # error-finished with a named reason
        if req.grammar is not None:
            # catch the automaton up over the tokens the prefill half
            # already delivered (the seeded first token): the migrated
            # stream must continue under the same constraint
            req._gq = req.grammar.walk(req.tokens)
            if req._gq < 0:
                self.queue.remove(req)
                self._finish_error(
                    req, "migrated tokens violate the request's grammar",
                    self.metrics.on_failure, "failed")
                if aid:
                    self.engine.adapter_bank.release(aid)
                return True
        self.queue.popleft()
        corr = self._corr(req)
        fresh = [self.pages.alloc() for _ in range(n_pg)]
        # evictions from the alloc burst spill before inject overwrites
        self._spill_evicted()
        row = np.full(self.engine.n_ptab, GARBAGE_PAGE, np.int32)
        row[:n_pg] = fresh
        t0 = time.perf_counter()
        try:
            with self.observer.span("prefill", slot=slot, suffix_len=0,
                                    cached=len(req.prompt), **corr):
                self.arena, self.last_tokens = self.engine.inject_pages(
                    self.arena, self.last_tokens, payload["data"],
                    fresh, slot, len(req.prompt),
                    int(payload["first_token"]))
        except Exception as e:
            self._contain(e)
            self._finish_error(
                req, f"engine failure: {self.last_engine_error}",
                self.metrics.on_failure, "failed")
            if aid:       # not slotted yet — _contain missed it
                self.engine.adapter_bank.release(aid)
            return True
        self._ptab[slot] = row
        self._slot_pages[slot] = list(fresh)
        # re-register the migrated FULL prompt pages under their chain
        # hashes: the target's prefix cache serves later identical
        # prompts locally (first-writer-wins, exactly as at prefill —
        # the satellite's "re-registered in the target allocator")
        if self.pages.prefix_cache:
            prompt = [int(t) for t in req.prompt]
            for h, p in zip(self.pages.page_hashes(prompt), fresh):
                self.pages.register(h, int(p))
                self.kv_receipts.append(("add", h))
        self.metrics.on_kv_handoff(n_pg, time.perf_counter() - t0)
        sp = req.sampling
        self.slots[slot] = req
        self._active[slot] = True
        self._aids[slot] = aid
        self._state[slot] = _SlotState(req.rid, len(req.prompt),
                                       req.speculate)
        self._temp[slot] = sp.temperature
        self._topk[slot] = sp.top_k
        self._topp[slot] = sp.top_p
        req.t_admit = time.perf_counter()
        req.admit_step = self.step_count
        self.observer.event("kv_handoff", side="inject", pages=n_pg,
                            **corr)
        self.observer.event("request_admitted", slot=slot,
                            step=self.step_count,
                            prompt_len=len(req.prompt),
                            cached=len(req.prompt),
                            lineage=req.lineage, **corr)
        self.observer.flow(
            "req", corr["rid"],
            "step" if req.origin_rid is not None else "start")
        # the first token was delivered by the prefill half (seeded in
        # req.tokens by the Router); this slot owes the remainder
        req._guaranteed = max(1, req._guaranteed)
        self.metrics.on_admit(req, slot, 0)
        if req._guaranteed >= self._budget(req):
            self._retire(slot)
        return True

    # ---- paged growth -------------------------------------------------

    def _grow_pages(self, step_act, lens):
        """Map pages covering every STEPPED slot's worst-case write
        window ``[0, pos_hi + draft_len + 1)`` before dispatch
        (``step_act`` is this round's dispatch mask — decoding slots
        plus the prefilling slots that drew a chunk; ``lens`` is the
        per-slot draft/chunk width minus one of the upcoming verify
        step, or None for a plain decode step).  Growth is host
        arithmetic over the same worst-case indices the overflow
        settling already tracks — no device reads, no new programs (the
        fresh table rides into the next dispatch as data).  A slot the
        pool cannot grow for — free list dry AND nothing evictable — is
        **shed** with the named :class:`PagePoolExhaustedError` message
        (``req.error``, counted in ``requests_shed``) and its pages
        free immediately, so the remaining traffic keeps stepping; the
        capacity signal is the error string, not a stall."""
        pg = self.engine.page_size
        for slot, req in enumerate(self.slots):
            if req is None or not step_act[slot]:
                continue
            st = self._state[slot]
            width = 1 + (int(lens[slot]) if lens is not None else 0)
            # pos_hi is a worst-case bound that runs one ahead of the
            # true engine index (the admission pseudo-window settles
            # into it), so near max_seq it can demand a page past the
            # table.  Clamp to the table: the kernel clamps any
            # actually-out-of-range write to position max_seq - 1,
            # which is always in the slot's own LAST page — never a
            # shared one, since prefix hits are capped at
            # (prompt_len - 1) // page_size full pages — and such
            # writes are post-budget garbage the harvest ignores
            # (exactly the dense arena's clamped-write discipline).
            need = min(-(-(st.pos_hi + width) // pg),
                       self.engine.n_ptab)
            pages = self._slot_pages[slot]
            try:
                while len(pages) < need:
                    p = self.pages.alloc()
                    self._ptab[slot, len(pages)] = p
                    pages.append(p)
            except PagePoolExhaustedError as e:
                self._finish_error(
                    req, f"{e} (shed after {len(req.tokens)} harvested "
                         f"tokens)", self.metrics.on_shed, "shed")
                self.observer.event("page_pool_shed", slot=slot,
                                    **self._corr(req))
                self._retire(slot)
        # growth may have evicted cached pages; spill them before the
        # caller's dispatch rewrites them
        self._spill_evicted()

    def _spill_evicted(self) -> None:
        """Drain the allocator's pending evictions into the spill store
        with ONE batched extract (round 23).  Must run after any alloc
        burst and BEFORE the next program dispatch rewrites the evicted
        pages — ``extract_pages_batch`` is a host sync, so the payloads
        are safely on the host before anything else reaches the device
        stream.  Best-effort by design: a failure here drops the
        payloads (those prefixes recompute later) and never breaks
        admission or a live decode."""
        if self.spill is None or self.pages is None \
                or not self.pages.pending_spills:
            return
        evs = self.pages.pending_spills
        self.pages.pending_spills = []
        t0 = time.perf_counter()
        try:
            data = self.engine.extract_pages_batch(
                self.arena, [p for _, p in evs])
        except Exception:
            return
        dt = time.perf_counter() - t0
        nbytes = 0
        for i, (h, _) in enumerate(evs):
            payload = jax.tree.map(lambda a, i=i: a[i:i + 1], data)
            nbytes += payload_nbytes(payload)
            self.spill.put(h, payload)
        self.metrics.on_spill(len(evs), nbytes, dt)
        self.observer.event("page_spilled", pages=len(evs),
                            nbytes=nbytes, host_pages=len(self.spill))

    # ---- drafting -----------------------------------------------------

    def _spec_desires(self):
        """Per-slot speculative draft desires ``{slot: k}`` for this
        step, over DECODING slots only (a prefilling slot has nothing
        to speculate about yet), each already clamped to its own room,
        budget, and adaptive k."""
        max_seq = self.engine.max_seq
        desires = {}
        for slot, req in enumerate(self.slots):
            if not self._active[slot]:
                continue
            st = self._state[slot]
            if st.prefilling or not req.speculate:
                continue
            room = max_seq - 1 - st.pos_hi
            remaining = self._budget(req) - req._guaranteed
            des = min(st.k_cur, req.speculate, remaining - 1, room)
            if des > 0:
                desires[slot] = des
        return desires

    def _plan_chunks(self):
        """Choose this step's prefill chunks ``{slot: width}`` under the
        per-step token budget (``chunk_tokens``), FIFO over the
        prefilling slots.  The one sequencing rule: a prompt that fills
        ``max_seq`` to the brim must never be left a 1-token final
        chunk — a verify window there (always >= 2 positions wide)
        would clamp backward over the prompt's own written positions —
        so the penultimate chunk shrinks (or the final pair goes out
        atomically, overshooting the budget by one token)."""
        if self.chunk_tokens is None:
            return {}
        max_seq = self.engine.max_seq
        plan = {}
        budget = self.chunk_tokens
        filling = [s for s in range(self.engine.n_slots)
                   if self._active[s] and self._state[s] is not None
                   and self._state[s].prefilling]
        for slot in sorted(filling, key=lambda s: self._state[s].rid):
            if budget < 1:
                break
            st = self._state[slot]
            remaining = st.fill_end - st.fill_next
            w = min(budget, remaining)
            if st.fill_end == max_seq and remaining - w == 1:
                w = remaining - 2 if remaining > 2 else 2
            plan[slot] = w
            budget -= w
        return plan

    # ---- the decode round --------------------------------------------

    def step(self) -> int:
        """One watchdog + admit + draft + decode/verify round; returns
        how many slots stepped.  Engine failures are contained to the
        in-flight batch (see :meth:`_contain`); deadline-expired
        requests retire with ``req.error`` before any work is spent on
        them this round."""
        self._expire()
        with self.observer.span("admit"):
            self._admit()
        # overflow settling: a speculative slot's worst-case index may
        # not leave room to write even one token — settle in-flight
        # steps until it does (only ever within k of max_seq)
        while self._pending and any(
                self._state[s].pos_hi > self.engine.max_seq - 1
                for s in range(self.engine.n_slots) if self._active[s]):
            with self.observer.span("harvest", forced=1):
                self._harvest_one()
        n_active = int(self._active.sum())
        if n_active:
            try:
                self._dispatch_round(n_active)
            except Exception as e:
                # containment: fail the in-flight batch, keep serving
                self._contain(e)
        self.step_count += 1
        self.metrics.on_step(n_active, self.engine.n_slots)
        if self.pages is not None:
            self.metrics.on_pages(self.pages.pages_in_use,
                                  self.pages.capacity)
        if len(self._pending) > self.harvest_lag:
            with self.observer.span("harvest"):
                while len(self._pending) > self.harvest_lag:
                    self._harvest_one()
        elif not n_active and self._pending:
            # nothing is decoding, so the lag buys no pipelining: a
            # parked prefill_only slot (awaiting its first-token
            # harvest to hand off) would otherwise sit under the lag
            # threshold forever
            with self.observer.span("harvest", idle=1):
                self._harvest_one()
        if self.exporter is not None:
            # harvest boundary: the metrics this samples were already
            # settled by the lag harvest above — host counters only,
            # and the exporter's own interval throttle decides whether
            # this boundary becomes a series point
            self.exporter.sample()
        return n_active

    def _dispatch_round(self, n_active: int):
        """The draft/chunk planning + decode/verify dispatch of one
        round (factored out so step() can contain an engine failure to
        this batch).  One compiled step serves the whole mix: decoding
        slots ride as before (plain or speculative), prefilling slots
        that drew a chunk this step ride the SAME verify program as
        forced rows (round 19) — so a long prompt's admission costs
        each decode step at most ``chunk_tokens`` of extra compute
        instead of a whole-prompt prefill stall."""
        B = self.engine.n_slots
        max_seq = self.engine.max_seq
        desires = self._spec_desires()
        chunk_plan = self._plan_chunks()
        # the step mask: decoding slots always; prefilling slots only
        # when they drew a chunk (their index must not advance a step
        # they are not part of)
        step_act = self._active.copy()
        for slot in range(B):
            st = self._state[slot]
            if st is not None and step_act[slot] and st.prefilling \
                    and slot not in chunk_plan:
                step_act[slot] = False
        # grammar gate: a constrained slot dispatches only when nothing
        # of its own is in flight — the token mask is a function of the
        # automaton state, which is exact only over HARVESTED truth.
        # Prefill chunks are exempt (prompt truth carries no automaton
        # state).  Speculation recovers the throughput the gate costs:
        # the one outstanding verify step still commits up to k+1
        # tokens, all masked by walking the DFA along the draft.
        gated = False
        for slot in range(B):
            req, st = self.slots[slot], self._state[slot]
            if req is None or req.grammar is None or not step_act[slot] \
                    or st.prefilling:
                continue
            if st.inflight:
                step_act[slot] = False
                desires.pop(slot, None)
                gated = True
        if not step_act.any():
            if gated and self._pending:
                # settle the oldest window so the gated automata advance
                # and the next round can dispatch them — without this a
                # lone constrained slot would never reach the lag
                # threshold and the loop would spin forever
                with self.observer.span("harvest", grammar=1):
                    self._harvest_one()
            return
        # the room bound covers EVERY active slot, stepped or not: the
        # dense verify scatter writes its k_prog+1 window into every
        # row (inactive rows write garbage at their own index), and a
        # window overflowing max_seq would CLAMP backward over a
        # sitting-out slot's committed prompt K/V — paged engines route
        # inactive writes to the garbage page, dense rows have no such
        # shield, so the transformer-layer contract (pos + s_new <=
        # max_seq for every row) is enforced fleet-wide here
        k_room = min(max_seq - 1 - self._state[s].pos_hi
                     for s in range(B) if self._active[s])
        if k_room < 1 and (desires or chunk_plan):
            # some stepped slot has room for exactly one more token (it
            # retires on this write): no k>=1 verify window fits, so
            # spec waits and chunks sit out one round — plain decode
            # clears the full slot and the next round resumes
            desires, chunk_plan = {}, {}
            for slot in range(B):
                st = self._state[slot]
                if st is not None and step_act[slot] and st.prefilling:
                    step_act[slot] = False
            if not step_act.any():
                return
        k_need = max([0] + list(desires.values())
                     + [w - 1 for w in chunk_plan.values()]
                     + ([1] if chunk_plan else []))
        drafts = lens = None
        n_drafted = 0
        if k_need > 0:
            k_prog = 1
            while k_prog < k_need:
                k_prog *= 2
            while k_prog > k_room and k_prog > 1:
                k_prog //= 2
            # re-cap chunks to the final program width (another slot's
            # room may have shrunk k_prog below the planned width)
            for slot in list(chunk_plan):
                st = self._state[slot]
                w = min(chunk_plan[slot], k_prog + 1)
                remaining = st.fill_end - st.fill_next
                if st.fill_end == max_seq and remaining - w == 1:
                    w -= 1          # never strand a 1-token final chunk
                if w < 1:
                    del chunk_plan[slot]
                    step_act[slot] = False
                else:
                    chunk_plan[slot] = w
            if not step_act.any():
                return
            drafts = np.zeros((B, k_prog), np.int32)
            lens = np.zeros(B, np.int32)
            forced = np.zeros(B, bool)
            first_tok = np.zeros(B, np.int32)
            pos_set = np.zeros(B, np.int32)
            t_draft = time.perf_counter()
            with self.observer.span("draft", n_active=n_active):
                for slot, des in desires.items():
                    req, st = self.slots[slot], self._state[slot]
                    want = min(des, k_prog)
                    gap = st.gap_est
                    # audit: ok[host-sync-asarray] drafting context from host prompt/token lists
                    ctx = np.asarray(list(req.prompt) + req.tokens,
                                     np.int32)
                    # audit: ok[host-sync-asarray] host-side draft source output (draft_s meters this phase)
                    pred = np.asarray(
                        self.draft.propose(ctx, gap + want), np.int32)
                    cand = pred[gap:gap + want]   # skip in-flight gap
                    if req.grammar is not None:
                        # trim at the first illegal draft token: the
                        # verify mask would reject everything from it
                        # on anyway (wasted k), and a shorter draft
                        # keeps the acceptance EMA honest.  gap is 0
                        # here (the grammar gate dispatches only with
                        # an empty inflight queue) so ``req._gq`` is
                        # exactly the state the draft continues from.
                        q, keep = req._gq, 0
                        for t in cand:
                            q = req.grammar.step(q, int(t))
                            if q < 0:
                                break
                            keep += 1
                        if keep < cand.size:
                            self.metrics.on_grammar_reject(
                                int(cand.size) - keep)
                            cand = cand[:keep]
                    dl = int(cand.size)
                    drafts[slot, :dl] = cand
                    lens[slot] = dl
                    n_drafted += dl
            self.metrics.on_draft(time.perf_counter() - t_draft)
            for slot, w in chunk_plan.items():
                st = self._state[slot]
                toks = st.fill_toks[st.fill_next:st.fill_next + w]
                first_tok[slot] = toks[0]
                drafts[slot, :w - 1] = toks[1:]
                lens[slot] = w - 1
                forced[slot] = True
                pos_set[slot] = st.fill_next
            if n_drafted == 0 and not chunk_plan:
                k_need = 0           # drafts came back empty: decode
        tables = None
        if self.pages is not None:
            self._grow_pages(step_act, lens if k_need > 0 else None)
            step_act &= self._active     # growth may have shed slots
            if not step_act.any():
                return
            tables = self._ptab          # the engine snapshots it at dispatch
        if k_need > 0:
            entries = []
            for slot in range(B):
                if not step_act[slot]:
                    continue
                req = self.slots[slot]
                if slot in chunk_plan:
                    st = self._state[slot]
                    w = chunk_plan[slot]
                    final = st.fill_next + w == st.fill_end
                    # kind 1 = intermediate chunk (nothing delivered),
                    # kind 2 = final chunk (deliver the bonus = the
                    # request's first token); dl rides as 0 so the
                    # harvest never counts prompt truth as speculation
                    entries.append((slot, req.rid, 0, 2 if final else 1))
                else:
                    entries.append((slot, req.rid, int(lens[slot]), 0))
            entries = tuple(entries)
            g_allowed = self._grammar_masks(step_act, chunk_plan,
                                            drafts, lens, k_prog)
            with self.observer.span("verify", n_active=n_active,
                                    k=k_prog):
                (self.arena, self.last_tokens, window,
                 counts) = self.engine.verify(
                    self.arena, self.last_tokens, drafts, lens,
                    step_act, self._next_key(), self._temp,
                    self._topk, self._topp, page_tables=tables,
                    forced=forced, first_tok=first_tok,
                    pos_set=pos_set, allowed=g_allowed,
                    adapter_ids=(self._aids if self.engine.adapter_bank
                                 is not None else None))
            self._pending.append((window, counts, entries))
            if n_drafted:
                self.metrics.on_verify(k_prog)
            for slot, rid, dl, kind in entries:
                st = self._state[slot]
                if kind == 0:
                    st.dispatched(dl)
                    continue
                w = chunk_plan[slot]
                st.dispatched(w - 1, kind)   # worst-case index += w;
                st.fill_next += w            # output gap += 0 or 1
                self.metrics.on_chunk(w)
                if kind == 2 and self.pages is not None \
                        and self._slot_hashes[slot] is not None:
                    # prompt fully dispatched: publish its pages under
                    # their chain hashes now (single device stream —
                    # any later prefix-hit attend is ordered after
                    # these writes)
                    hashes, n_hits = self._slot_hashes[slot]
                    row = self._ptab[slot]
                    for i in range(n_hits, len(hashes)):
                        self.pages.register(hashes[i], int(row[i]))
                        self.kv_receipts.append(("add", hashes[i]))
                    self._slot_hashes[slot] = None
        else:
            entries = tuple(
                (slot, req.rid, 0, 0)
                for slot, req in enumerate(self.slots)
                if step_act[slot])
            g_allowed = None
            g_rows = [s for s in range(B) if step_act[s]
                      and self.slots[s] is not None
                      and self.slots[s].grammar is not None]
            if g_rows:
                g_allowed = np.ones(
                    (B, self.engine.model.vocab_size), bool)
                for s in g_rows:
                    r = self.slots[s]
                    g_allowed[s] = r.grammar.mask(r._gq)
            with self.observer.span("dispatch", n_active=n_active):
                self.arena, self.last_tokens, _ = self.engine.decode(
                    self.arena, self.last_tokens, step_act,
                    self._next_key(), self._temp, self._topk,
                    self._topp, page_tables=tables, allowed=g_allowed,
                    adapter_ids=(self._aids if self.engine.adapter_bank
                                 is not None else None))
            self._pending.append((self.last_tokens, None, entries))
            for slot, rid, _, _ in entries:
                self._state[slot].dispatched(0)
        for slot, rid, dl, kind in entries:
            if kind == 1:
                continue             # no token guaranteed by a chunk
            req = self.slots[slot]
            req._guaranteed += 1
            if req._guaranteed >= self._budget(req):
                self._retire(slot)
            elif kind == 2 and req.prefill_only:
                # prefill-role replica: park until the first token
                # harvests and the page payload is extracted
                self._active[slot] = False

    def _grammar_masks(self, step_act, chunk_plan, drafts, lens,
                       k_prog):
        """Per-position allowed-token masks for one verify step, or
        None when no stepped slot is grammar-constrained (the engine
        then reuses its cached all-true mask — nothing uploads).

        Rows are host numpy slices of each DFA's precomputed ``allow``
        table — building the [B, k+1, V] block is pure host indexing at
        the dispatch boundary, uploaded as data like the page tables.
        For a decode/spec row, position 0 masks from the harvested
        state and each later position from the state after the
        corresponding (pre-trimmed, hence legal) draft token; for a
        chunk row only the FINAL chunk's bonus position is constrained
        (the request's first output token — start-state mask), prompt
        echo positions are forced-accept and stay all-true."""
        B = self.engine.n_slots
        rows = [s for s in range(B) if step_act[s]
                and self.slots[s] is not None
                and self.slots[s].grammar is not None]
        if not rows:
            return None
        allowed = np.ones((B, k_prog + 1,
                           self.engine.model.vocab_size), bool)
        for slot in rows:
            req = self.slots[slot]
            dfa = req.grammar
            if slot in chunk_plan:
                st = self._state[slot]
                w = chunk_plan[slot]
                if st.fill_next + w == st.fill_end:
                    allowed[slot, w - 1] = dfa.mask(dfa.start)
                continue
            q = req._gq
            allowed[slot, 0] = dfa.mask(q)
            for i in range(int(lens[slot])):
                q = dfa.step(q, int(drafts[slot, i]))
                allowed[slot, i + 1] = dfa.mask(q)
        return allowed

    # ---- harvest ------------------------------------------------------

    def _harvest_one(self):
        window, counts, entries = self._pending.popleft()
        # audit: ok[host-sync-asarray] the lag harvest — blocks only until the k-steps-lagged window
        arr = np.asarray(window)  # blocks only until THIS (lagged) step
        # audit: ok[host-sync-asarray] the lag harvest — the sanctioned boundary read (counts)
        cnt = np.asarray(counts) if counts is not None else None
        now = time.perf_counter()
        for slot, rid, dl, kind in entries:
            req = self._reqs[rid]
            n_em = int(cnt[slot]) if cnt is not None else 1
            if kind == 1:
                # intermediate prefill chunk: the window is prompt echo
                # plus a throwaway bonus prediction — nothing delivered
                toks = arr[slot, :0]
            elif kind == 2:
                # final prefill chunk: deliver ONLY the bonus sample —
                # the request's first generated token (the prompt echo
                # before it committed to cache, not to output)
                toks = arr[slot, n_em - 1:n_em]
            else:
                toks = (arr[slot, :n_em] if arr.ndim == 2
                        else arr[slot:slot + 1])
            st = self._state[slot]
            if st is not None and st.rid == rid:
                st.settle(dl, n_em)
            if dl:
                self.metrics.on_spec_harvest(dl, n_em - 1)
            if req.done:         # post-eos/budget garbage from the lag
                continue         # window (or spec overshoot)
            budget = self._budget(req)
            first_window = len(req.tokens) == 0
            delivered = 0
            for t in toks:
                req.tokens.append(int(t))
                delivered += 1
                if req.grammar is not None:
                    # advance the automaton over the delivered token —
                    # this is the state every later dispatch masks
                    # from.  A rejection here is defense in depth (the
                    # dispatch masks make it unreachable for sampled
                    # tokens): contain it as a failed request, never
                    # deliver the illegal token.
                    q = req.grammar.step(req._gq, int(t))
                    if q < 0:
                        req.tokens.pop()
                        delivered -= 1
                        self.observer.event(
                            "grammar_violation", token=int(t),
                            reason="illegal", **self._corr(req))
                        self._finish_error(
                            req, f"grammar violation: token {int(t)} "
                                 f"is illegal in automaton state "
                                 f"{req._gq}",
                            self.metrics.on_failure, "failed")
                        break
                    req._gq = q
                if len(req.tokens) == 1:
                    req.t_first = now
                    self.metrics.on_first_token(req)
                    self.observer.event("request_first_token",
                                        slot=slot, **self._corr(req))
                hit_eos = (req.eos_id is not None
                           and req.tokens[-1] == req.eos_id)
                if hit_eos or len(req.tokens) >= budget:
                    req.done = True
                    req.t_done = now
                    self.finished.append(req)
                    self.metrics.on_finish(req)
                    corr = self._corr(req)
                    self.observer.event("request_finished",
                                        tokens=len(req.tokens),
                                        eos=int(hit_eos), **corr)
                    if req.grammar is not None \
                            and not req.grammar.accept[req._gq]:
                        # token budget ran out mid-structure: the
                        # output is legal-so-far but not a complete
                        # utterance of the grammar — observable, not
                        # an error (EOS can only land in accepting
                        # states, so this is always a truncation)
                        self.observer.event("grammar_violation",
                                            reason="incomplete", **corr)
                    self.observer.flow(
                        "req", corr["rid"],
                        "step" if req.origin_rid is not None else "end")
                    break        # EOS mid-window trims exactly
            # decode-token accounting counts DELIVERED generated tokens
            # (the request's very first token is the prefill's)
            self.metrics.on_harvest_tokens(
                delivered - (1 if first_window and delivered else 0))
            if delivered:
                self.metrics.on_adapter_tokens(req.adapter or "base",
                                               delivered)
                if req.stream is not None:
                    # incremental delivery from the lag-harvested
                    # window: first offerer owns the stream (hedge
                    # losers get 0), extensions are prefix-guarded
                    n = req.stream.offer(req.rid, req.tokens)
                    if n:
                        self.metrics.on_stream(n)
                        self.observer.event("stream_delivery", tokens=n,
                                            **self._corr(req))
            if req.prefill_only and not req.done and req.tokens:
                # prefill-role completion: first token known, more
                # generation owed — export the page payload for the
                # decode half of the flight (round 19)
                self._handoff_out(slot, req)
            if req.done and req.error is None:
                # success terminal: a standalone request closes its
                # stream here (reconciling any unoffered suffix); a
                # fleet attempt leaves it to the Router's _finish_user
                self._stream_terminal(req)
            if req.done and self.slots[slot] is req:
                self._retire(slot)

    def _handoff_out(self, slot: int, req: Request):
        """Finish a ``prefill_only`` request by exporting its prompt's
        K/V pages to host (the ONE deliberate sync of the handoff path
        — its cost is the ``kv_handoff_s`` metric) and attaching the
        payload a decode replica's ``kv_inject`` admission adopts.  The
        slot's pages are released only after extraction (the caller's
        retire), so a mid-handoff expiry can never free them early."""
        pg = self.engine.page_size
        n_pg = -(-len(req.prompt) // pg)
        pages = self._slot_pages[slot][:n_pg]
        t0 = time.perf_counter()
        data = self.engine.extract_pages(self.arena, pages)
        dt = time.perf_counter() - t0
        req.kv_handoff = {
            "prompt": [int(t) for t in req.prompt],
            "first_token": int(req.tokens[0]),
            "n_pages": n_pg,
            "data": data,
            "t_first": req.t_first,
        }
        self.metrics.on_kv_handoff(n_pg, dt)
        corr = self._corr(req)
        self.observer.event("kv_handoff", side="extract", pages=n_pg,
                            **corr)
        req.done = True
        req.t_done = time.perf_counter()
        self.finished.append(req)
        self.metrics.on_finish(req)
        self.observer.event("request_finished", tokens=len(req.tokens),
                            eos=0, **corr)
        self.observer.flow(
            "req", corr["rid"],
            "step" if req.origin_rid is not None else "end")

    def drain(self):
        """Harvest everything still in flight (the boundary sync)."""
        with self.observer.span("drain"):
            while self._pending:
                self._harvest_one()
        if self.exporter is not None:
            self.exporter.sample()

    # ---- shutdown -----------------------------------------------------

    def shutdown(self, drain: bool = True) -> None:
        """Stop the intake and wind the scheduler down.

        ``drain=True`` (graceful): queued-but-unadmitted requests are
        aborted with a named error (they never started; re-submittable
        elsewhere), in-flight requests run to completion, and every
        pending harvest settles — no generated token is lost.
        ``drain=False`` (abort): no further steps are dispatched;
        already-computed harvest windows are still settled (pure host
        reads — a request that only awaited the lag harvest finishes
        cleanly instead of being orphaned), then the remaining in-flight
        requests retire with ``req.error`` set.  Idempotent; ``submit``
        after shutdown rejects.
        """
        already = self._closed
        self._closed = True
        while self.queue:
            # on_abort, not on_reject: these were counted by on_submit
            # already — on_reject's n_submitted increment would double-
            # count them and break the submitted == finished+rejected+
            # expired+failed+aborted invariant
            self._finish_error(self.queue.popleft(),
                               "scheduler shut down before admission",
                               self.metrics.on_abort, "aborted")
        if already:
            return
        self.observer.event("scheduler_shutdown", drain=int(drain))
        if drain:
            while any(s is not None for s in self.slots):
                self.step()
            self.drain()
            if self.exporter is not None:
                self.exporter.sample(force=True)   # the final point
            return
        self.drain()     # settle what the device already computed
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            # a deliberate abort, not an engine failure: counted under
            # requests_aborted so the failure alert stays meaningful
            self._finish_error(req, "scheduler shut down",
                               self.metrics.on_abort, "aborted")
            self._retire(slot)
        if self.exporter is not None:
            self.exporter.sample(force=True)

    def __enter__(self) -> "Scheduler":
        return self

    def __exit__(self, exc_type, *exc) -> bool:
        # clean exit drains gracefully; an exception aborts (stepping a
        # possibly-broken engine to drain would compound the failure)
        self.shutdown(drain=exc_type is None)
        return False

    # ---- driver -------------------------------------------------------

    def run(self, requests: Sequence[Request] = ()) -> list[Request]:
        for r in requests:
            self.submit(r)
        while self.queue or any(s is not None for s in self.slots):
            self.step()
        self.drain()
        return self.finished
