"""Serving telemetry under the PR-1 async dispatch discipline.

Nothing in here syncs the device per token.  Three kinds of signal, each
with an honest clock:

* **Dispatch-side counters** (prefills, decode steps, slot occupancy) —
  pure host state the scheduler already knows; pushed per step into the
  existing :class:`~dtdl_tpu.metrics.device.MetricsQueue` and drained at
  summary, so a future device-scalar metric (e.g. an in-program
  accept-rate) rides the same bounded-lag queue instead of growing a new
  sync point.
* **Harvest-side request timing** (TTFT, per-token latency) — stamped
  when a token *reaches the host* through the scheduler's lag harvest,
  i.e. at the first moment the serving process could actually have
  observed it.  With ``harvest_lag=k`` these run up to k steps late;
  ``Scheduler.drain`` settles them exactly at boundaries.
* **Throughput** (prefill/decode tokens per second) — wall-clock between
  the first dispatch and the last harvest: a fetch ends the timed
  region, as in ``benchmarks/runners/train.py``.

Tail percentiles (TTFT / per-token latency p50/p95/p99) come from
streaming log-bucketed histograms (:class:`dtdl_tpu.obs.hist.
LogHistogram`): fixed memory under unbounded traffic, fed with the same
lag-harvested host floats as the means — zero added per-token device
syncs.  Like every harvest-side number they run up to ``harvest_lag``
steps late; ``Scheduler.drain`` settles them exactly.
"""

from __future__ import annotations

import time

from dtdl_tpu.metrics.device import MetricsQueue
from dtdl_tpu.obs.hist import LogHistogram

# exact per-request samples kept for tests/small runs; past this cap only
# the fixed-memory histograms (which see EVERY sample) keep growing stats
_MAX_SAMPLES = 65536

# ---------------------------------------------------------------------------
# terminal error kinds — the one place that knows the ``req.error``
# prefix grammar.  Every terminal error is "<kind>: <reason>" (PR 9);
# callers branch through error_kind() instead of scattering
# string-splitting (the fleet Router, the exporter/SLO availability
# accounting, and Scheduler._finish_error all share this list).
# ---------------------------------------------------------------------------

ERROR_KINDS = ("rejected", "expired", "failed", "aborted", "shed")

# which kinds count AGAINST availability in the SLO layer: failed
# (engine/replica health) and expired (the service blew the deadline)
# are service faults; rejected/shed are deliberate load management and
# aborted is a caller/shutdown decision — charging those to
# availability would make every graceful drain an outage
UNAVAILABLE_KINDS = ("failed", "expired")


def error_kind(error) -> str | None:
    """The machine-checkable kind prefix of a terminal ``req.error``
    (one of :data:`ERROR_KINDS`), or None for no error / an unprefixed
    string.  The single string-parsing point for the kind grammar."""
    if not error:
        return None
    kind = error.split(":", 1)[0]
    return kind if kind in ERROR_KINDS else None


def _window_delta(summary: dict, counters, prev: dict) -> dict:
    """Flatten ``summary`` to numeric scalars, replacing each field in
    ``counters`` with its increment since the last call (state in
    ``prev``, updated in place).  Gauges/tails pass through at their
    current value; bools become 0/1 ints; nested dicts/lists are
    dropped (a time series point is flat by contract)."""
    out = {}
    for k, v in summary.items():
        if isinstance(v, bool):
            out[k] = int(v)
        elif isinstance(v, (int, float)):
            out[k] = v - prev.get(k, 0) if k in counters else v
        elif isinstance(v, dict) and k in counters:
            # dict-valued counter (tokens_by_adapter, round 22):
            # flatten to per-key scalar deltas — a series point stays
            # flat, and each tenant gets its own series
            for kk, vv in v.items():
                if isinstance(vv, (int, float)):
                    fk = f"{k}.{kk}"
                    out[fk] = vv - prev.get(fk, 0)
                    prev[fk] = vv
    prev.update({k: summary[k] for k in counters
                 if isinstance(summary.get(k), (int, float))})
    return out


class ServeMetrics:
    """Scheduler-driven serving telemetry (see module docstring)."""

    def __init__(self, queue: MetricsQueue = None, n_slots: int = 0):
        self.queue = queue or MetricsQueue()
        self.n_slots = n_slots
        self.n_submitted = 0
        self.n_rejected = 0
        self.n_expired = 0      # deadline watchdog retirements
        self.n_failed = 0       # engine-failure containment retirements
        self.n_aborted = 0      # in-flight at a drain=False shutdown
        self.n_admitted = 0
        self.n_finished = 0
        self.n_decode_steps = 0
        self.decode_slot_steps = 0      # sum of active slots over steps
        self.decode_tokens_delivered = 0  # harvested generated tokens
        self.prefill_tokens = 0
        # speculative decoding (lag-harvested, like everything else):
        # drafted vs accepted candidate counts, verify step count, and
        # the host time spent inside DraftSource.propose — the honest
        # draft-overhead ledger against the accepted-token win
        self.n_verify_steps = 0
        self.verify_steps_by_k: dict[int, int] = {}
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.draft_s = 0.0
        # paged KV arena (dtdl_tpu/serve/paged.py): prefix-cache hit
        # accounting over FULL prompt pages, prefill tokens the cache
        # let the engine skip, page-pool occupancy (host counters the
        # scheduler already knows — no device reads), and requests shed
        # when the pool could not grow a mid-flight sequence
        self.n_shed = 0
        self.prefix_hit_pages = 0
        self.prefix_full_pages = 0
        self.prefill_tokens_saved = 0
        self.pages_in_use_peak = 0
        self.pages_in_use_last = 0
        self.page_capacity = 0
        # chunked prefill + disaggregation interference receipts (round
        # 19): chunk counts/tokens are the chunked path's ledger;
        # decode_steps_delayed_by_prefill is the PRE-change counter —
        # each whole-prompt (blocking) prefill charges the number of
        # in-flight decode slots it stalled, so a before/after run
        # can show the interference the chunked path removes;
        # kv_handoff_* meter the page-granular prefill→decode migration
        # (pages moved, seconds spent in the extract sync / inject
        # dispatch)
        self.n_prefill_chunks = 0
        self.n_chunk_tokens = 0
        self.n_decode_steps_delayed = 0
        self.n_kv_handoff_pages = 0
        self.kv_handoff_s = 0.0
        # hierarchical KV cache (round 23): pages demoted to the
        # host/disk spill tiers on eviction, pages restored from them on
        # a prefix miss (each restored page is prefill recompute the
        # hierarchy saved), bytes and host seconds both ways, per-tier
        # hit split, quarantined disk records, and requests routed here
        # by the fleet prefix directory
        self.pages_spilled = 0
        self.pages_restored = 0
        self.spill_bytes = 0
        self.restore_bytes = 0
        self.spill_s = 0.0
        self.restore_s = 0.0
        self.spill_host_hits = 0
        self.spill_disk_hits = 0
        self.spill_quarantined = 0
        self.directory_hits = 0
        # multi-tenant serving (round 22): delivered generated tokens
        # keyed by adapter name ("base" = no adapter), draft tokens the
        # grammar automaton trimmed before verify, and incremental
        # token deliveries pushed through per-request TokenStreams
        self.tokens_by_adapter: dict[str, int] = {}
        self.grammar_rejected_tokens = 0
        self.stream_deliveries = 0
        self.ttft_s: list[float] = []          # exact samples, capped
        self.tok_latency_s: list[float] = []   # per-request mean, capped
        # streaming stats (fixed memory, never capped): means AND tails
        # in summary() come from these, so they stay exact under
        # unbounded traffic while the sample lists stop at _MAX_SAMPLES
        self.ttft_hist = LogHistogram()
        self.tok_latency_hist = LogHistogram()
        self._t_start = None
        self._t_last_harvest = None
        self._occupancy: list[dict] = []
        self._win_prev: dict = {}      # window() delta baseline

    # ---- scheduler hooks ---------------------------------------------

    def on_submit(self, req):
        self.n_submitted += 1

    def on_reject(self, req):
        """Submit-time rejection (prompt past the largest bucket, full
        admission queue, shut-down scheduler — ``req.error`` carries the
        diagnosis)."""
        self.n_submitted += 1
        self.n_rejected += 1

    def on_expire(self, req):
        """Deadline-watchdog retirement (``req.deadline_s`` exceeded,
        queued or mid-decode) — the containment path that keeps one hung
        or over-budget request from occupying a slot forever."""
        self.n_expired += 1

    def on_failure(self, req):
        """Engine-failure containment: the request was in flight when a
        compiled program failed and retired with ``req.error`` set."""
        self.n_failed += 1

    def on_abort(self, req):
        """Aborted by shutdown — queued-but-unadmitted, or in flight at
        a non-draining shutdown — or cancelled by rid
        (:meth:`Scheduler.cancel`, e.g. the fleet Router's hedge-loser
        path).  A deliberate abort of an ALREADY SUBMITTED request:
        counted separately so ``requests_failed`` stays an
        engine-health signal and ``requests_submitted`` (which
        ``on_submit`` already incremented) is not double-counted."""
        self.n_aborted += 1

    def on_shed(self, req):
        """Page-pool exhaustion shed: the request was mid-flight when
        the pool could not supply a page for its next write window and
        no cached page was evictable — retired with ``req.error`` set
        (its pages freed; the run continues).  A capacity signal, kept
        apart from ``requests_failed`` (engine health) and
        ``requests_expired`` (per-request deadlines)."""
        self.n_shed += 1

    def on_prefix(self, hit_pages: int, full_pages: int,
                  tokens_saved: int):
        """One admission's prefix-cache outcome: of ``full_pages`` full
        prompt pages, ``hit_pages`` leading ones were already resident
        (mapped read-only, ``tokens_saved`` prompt tokens skipped
        prefill entirely)."""
        self.prefix_hit_pages += hit_pages
        self.prefix_full_pages += full_pages
        self.prefill_tokens_saved += tokens_saved

    def on_pages(self, pages_in_use: int, capacity: int):
        """Page-pool occupancy after a scheduler step (host-side
        allocator state, like slot occupancy — never a device read)."""
        self.pages_in_use_last = pages_in_use
        self.pages_in_use_peak = max(self.pages_in_use_peak, pages_in_use)
        self.page_capacity = capacity

    def on_chunk(self, tokens: int):
        """One prefill chunk dispatched at width ``tokens`` (round 19):
        prompt processing that shared a compiled step with the
        in-flight decodes instead of stalling them."""
        self.n_prefill_chunks += 1
        self.n_chunk_tokens += tokens

    def on_prefill_block(self, n_decoding: int):
        """One BLOCKING whole-prompt prefill dispatched while
        ``n_decoding`` slots were mid-decode — each of them waits a
        full prefill latency for their next token.  Zero under chunked
        prefill; the before/after interference receipt."""
        self.n_decode_steps_delayed += n_decoding

    def on_kv_handoff(self, pages: int, seconds: float):
        """One side of a prefill→decode page migration: ``pages`` moved
        (source extract or target inject), ``seconds`` of host time —
        the extract side's device_get is the one deliberate sync of the
        disaggregation path."""
        self.n_kv_handoff_pages += pages
        self.kv_handoff_s += seconds

    def on_spill(self, pages: int, nbytes: int, seconds: float):
        """One batched spill-on-evict: ``pages`` evicted pages extracted
        to the host tier in ONE device_get sync costing ``seconds`` of
        host time, ``nbytes`` moved.  The write half of the memory-
        hierarchy ledger."""
        self.pages_spilled += pages
        self.spill_bytes += nbytes
        self.spill_s += seconds

    def on_restore(self, pages: int, nbytes: int, seconds: float,
                   host_hits: int = 0, disk_hits: int = 0):
        """One admission's restore-from-spill: ``pages`` spilled pages
        re-entered the HBM arena through inject (dispatch-only — no
        sync), so their prompt tokens skipped recompute-prefill.
        ``host_hits``/``disk_hits`` split the pages by serving tier."""
        self.pages_restored += pages
        self.restore_bytes += nbytes
        self.restore_s += seconds
        self.spill_host_hits += host_hits
        self.spill_disk_hits += disk_hits

    def on_spill_quarantine(self, n: int):
        """``n`` disk spill records failed integrity and were
        quarantined by name (the affected prefixes fell back to
        recompute — a perf event, never a correctness one)."""
        self.spill_quarantined += n

    def on_directory_hit(self):
        """The fleet prefix directory routed a request here because
        this replica holds its prefix (affinity beat least-loaded)."""
        self.directory_hits += 1

    def on_draft(self, seconds: float):
        """One drafting phase's host time (dispatch-side; drafted/
        accepted token counts land at harvest via on_spec_harvest)."""
        self.draft_s += seconds

    def on_verify(self, k: int):
        """One verify step dispatched at draft-width bucket ``k``."""
        self.n_verify_steps += 1
        self.verify_steps_by_k[k] = self.verify_steps_by_k.get(k, 0) + 1

    def on_spec_harvest(self, drafted: int, accepted: int):
        """One slot's verify outcome, known at harvest: ``drafted``
        candidates were scored, ``accepted`` survived."""
        self.spec_drafted += drafted
        self.spec_accepted += accepted

    def on_adapter_tokens(self, adapter: str, n: int):
        """``n`` generated tokens harvested for a request served under
        ``adapter`` (``"base"`` when none) — the per-tenant goodput
        split of the same harvested-truth accounting as
        :meth:`on_harvest_tokens`."""
        self.tokens_by_adapter[adapter] = \
            self.tokens_by_adapter.get(adapter, 0) + n

    def on_grammar_reject(self, n: int):
        """``n`` draft tokens trimmed at dispatch because the grammar
        automaton rejects them — speculation burned against the
        constraint (the cost half of the constrained-decode ledger)."""
        self.grammar_rejected_tokens += n

    def on_stream(self, n: int):
        """``n`` tokens delivered incrementally through a request's
        TokenStream at one lag-harvest boundary."""
        self.stream_deliveries += n

    def on_harvest_tokens(self, n: int):
        """``n`` generated tokens delivered to a request at harvest
        (post-trim, excluding the prefill-sampled first token) — the
        decode-throughput numerator, which under speculative decoding
        counts exactly the ACCEPTED tokens."""
        self.decode_tokens_delivered += n

    def on_admit(self, req, slot: int, prompt_len: int):
        if self._t_start is None:
            self._t_start = time.perf_counter()
        self.n_admitted += 1
        self.prefill_tokens += prompt_len

    def on_step(self, n_active: int, n_slots: int):
        if n_active:
            self.n_decode_steps += 1
            self.decode_slot_steps += n_active
        self.n_slots = n_slots or self.n_slots
        # per-step entry through the bounded async queue; drained (not
        # read inline) at summary() — host scalars today, device scalars
        # tomorrow, same discipline either way
        self._occupancy.extend(
            self.queue.push({"n_active": float(n_active)}))

    def on_first_token(self, req):
        self._t_last_harvest = time.perf_counter()
        ttft = self._t_last_harvest - req.t_submit
        if len(self.ttft_s) < _MAX_SAMPLES:
            self.ttft_s.append(ttft)
        self.ttft_hist.add(ttft)

    def on_finish(self, req):
        self._t_last_harvest = time.perf_counter()
        self.n_finished += 1
        n_decoded = len(req.tokens) - 1
        if n_decoded > 0:
            per_tok = (req.t_done - req.t_first) / n_decoded
            if len(self.tok_latency_s) < _MAX_SAMPLES:
                self.tok_latency_s.append(per_tok)
            self.tok_latency_hist.add(per_tok)

    # ---- aggregation --------------------------------------------------

    def summary(self) -> dict:
        """Drain the step queue and aggregate; call after
        ``Scheduler.drain`` (or ``run``) so harvest times are settled."""
        self._occupancy.extend(self.queue.drain())
        # both endpoints or no window: before the first harvest there is
        # no honest wall-clock span to report
        wall = 0.0
        if self._t_start is not None and self._t_last_harvest is not None:
            wall = self._t_last_harvest - self._t_start
        decode_tokens = self.decode_tokens_delivered
        occ = [e["n_active"] for e in self._occupancy]
        mean = lambda xs: sum(xs) / len(xs) if xs else 0.0  # noqa: E731
        return {
            "requests_submitted": self.n_submitted,
            "requests_rejected": self.n_rejected,
            "requests_expired": self.n_expired,
            "requests_failed": self.n_failed,
            "requests_aborted": self.n_aborted,
            "requests_finished": self.n_finished,
            "prefill_tokens": self.prefill_tokens,
            "decode_steps": self.n_decode_steps,
            # delivered generated tokens: under speculative decoding this
            # counts ACCEPTED tokens, so tokens/sec below is the honest
            # spec-decode win (goodput counts real tokens, never drafts)
            "decode_tokens": decode_tokens,
            "wall_s": round(wall, 6),
            "decode_tokens_per_sec": round(decode_tokens / wall, 2)
            if wall > 0 else 0.0,
            "tokens_per_step_mean": round(
                decode_tokens / self.n_decode_steps, 4)
            if self.n_decode_steps else 0.0,
            "requests_shed": self.n_shed,
            # chunked prefill + disaggregation receipts (round 19)
            "prefill_chunks": self.n_prefill_chunks,
            "chunk_tokens": self.n_chunk_tokens,
            "decode_steps_delayed_by_prefill": self.n_decode_steps_delayed,
            "kv_handoff_pages": self.n_kv_handoff_pages,
            "kv_handoff_s": round(self.kv_handoff_s, 6),
            # hierarchical KV cache (round 23): the spill/restore ledger
            "pages_spilled": self.pages_spilled,
            "pages_restored": self.pages_restored,
            "spill_bytes": self.spill_bytes,
            "restore_bytes": self.restore_bytes,
            "spill_s": round(self.spill_s, 6),
            "restore_s": round(self.restore_s, 6),
            "spill_host_hits": self.spill_host_hits,
            "spill_disk_hits": self.spill_disk_hits,
            "spill_quarantined": self.spill_quarantined,
            "directory_hits": self.directory_hits,
            # multi-tenant serving (round 22): per-tenant goodput split
            # plus the constrained-decode and streaming ledgers
            "tokens_by_adapter": dict(self.tokens_by_adapter),
            "grammar_rejected_tokens": self.grammar_rejected_tokens,
            "stream_deliveries": self.stream_deliveries,
            # paged KV / prefix cache (all zeros for a dense arena):
            # hit rate is over FULL prompt pages — the unit of sharing
            "prefix_hit_rate": round(
                self.prefix_hit_pages / self.prefix_full_pages, 4)
            if self.prefix_full_pages else 0.0,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "pages_in_use_peak": self.pages_in_use_peak,
            "pages_in_use_last": self.pages_in_use_last,
            "page_capacity": self.page_capacity,
            "spec_steps": self.n_verify_steps,
            "spec_steps_by_k": dict(self.verify_steps_by_k),
            "spec_drafted_tokens": self.spec_drafted,
            "spec_accepted_tokens": self.spec_accepted,
            "spec_acceptance_rate": round(
                self.spec_accepted / self.spec_drafted, 4)
            if self.spec_drafted else 0.0,
            "draft_s": round(self.draft_s, 6),
            "occupancy_mean": round(
                mean(occ) / self.n_slots if self.n_slots else 0.0, 4),
            # lag-harvested latency means + tails from the histograms'
            # exact running stats (they see every sample even past the
            # capped lists); the 0.0 defaults keep the mean keys present
            # under zero traffic, where summary() emits no fields
            "ttft_s_mean": 0.0, "tok_latency_s_mean": 0.0,
            **self.ttft_hist.summary("ttft_s_"),
            **self.tok_latency_hist.summary("tok_latency_s_"),
        }

    # the monotonically-increasing summary fields window() diffs; rates,
    # occupancy, tails, and page gauges pass through at current value
    _WINDOW_COUNTERS = frozenset({
        "requests_submitted", "requests_rejected", "requests_expired",
        "requests_failed", "requests_aborted", "requests_finished",
        "requests_shed", "prefill_tokens", "decode_steps",
        "decode_tokens", "prefill_tokens_saved", "spec_steps",
        "spec_drafted_tokens", "spec_accepted_tokens", "draft_s",
        "prefill_chunks", "chunk_tokens",
        "decode_steps_delayed_by_prefill", "kv_handoff_pages",
        "kv_handoff_s", "tokens_by_adapter", "grammar_rejected_tokens",
        "stream_deliveries",
        # hierarchical KV cache (round 23)
        "pages_spilled", "pages_restored", "spill_bytes",
        "restore_bytes", "spill_s", "restore_s", "spill_host_hits",
        "spill_disk_hits", "spill_quarantined", "directory_hits",
    })

    def window(self) -> dict:
        """Counters since the last :meth:`window` call — the delta feed
        a continuous exporter samples at drain/harvest boundaries, so it
        never re-implements diffing.  Counter fields (see
        ``_WINDOW_COUNTERS``) come back as increments; everything else
        numeric (rates, tails, occupancy, page gauges) rides along at
        its current value, and non-scalar fields are dropped.  The
        cumulative :meth:`summary` contract is untouched — both read the
        same books; only this method keeps a baseline."""
        return _window_delta(self.summary(), self._WINDOW_COUNTERS,
                             self._win_prev)
