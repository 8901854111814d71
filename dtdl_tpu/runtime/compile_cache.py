"""Where the persistent XLA compilation cache lives.

Cold compiles dominate a short run (the 'large' LM train step alone is
about a minute on a v5e), and every process pays them again unless the
cache is on disk.  The location is a deployment setting, so it is placed
from OUTSIDE first: when ``JAX_COMPILATION_CACHE_DIR`` is in the
environment jax reads it itself and this module touches nothing.
Otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed path
derived from this file, never a temp dir, a pid or the time, because the
path is part of what makes a later process find the entries again.

Entry points call :func:`enable_compile_cache` once, before the first
compile (``examples/common.py:bootstrap``, ``chip_smoke.py``,
``benchmarks/runners/train.py``); nothing else in the repo sets a cache
directory.

**The compile account.**  The same call starts the account of what set-up
costs: jax reports every trace, lowering, backend compile and cache
look-up through ``jax.monitoring`` with the traced function's name, and
the listeners registered here keep one :class:`CompileRow` an event, in
memory, on the ``time.perf_counter`` clock.  Nothing is written anywhere
and the listeners run only when jax compiles, so a steady loop pays
nothing for them.  :func:`compile_account` returns the rows,
:func:`covered_s` the seconds a choice of them covers,
:func:`compile_totals` the totals (``Observer.summary()`` carries them;
``chip_smoke.py`` prints them: "why did this job take 100 s to start").
The account is one a process because jax's listeners are.

**The checkpoint plan.**  Beside the step's rows the account keeps what a
``remat=True`` model chose to keep each time a train step traced it
(``models/remat_plan.py``): :func:`record_remat_plan` appends,
:func:`remat_plans` returns them, and :func:`compile_totals` carries the
newest as ``remat_blocks_by_rung``, ``remat_kept_bytes``,
``remat_budget_bytes``, ``remat_estimate_bytes`` (held as the backward pass
begins), ``remat_start_by`` (``"head"`` where the logits and their cotangent
set that estimate, ``"block"`` where one block's live set did) and, where
the step's gradients are read together,
``remat_end_bytes`` and ``remat_walk_bytes`` (held as it ends, and the most
between the two instants: the plan was held to all three), and
``remat_moe_out_blocks``, ``remat_moe_out_bytes`` (how many blocks keep
their held experts' output for the norm that reads it, and its bytes: 0
where none does).  A held-experts layer
(``models/transformer.py:HeldExperts``) adds the shapes of its two row
buffers the same way: :func:`record_expert_buffer`, :func:`expert_buffers`,
and ``moe_buffer_rows``, ``moe_first_buffer_rows``, ``moe_row_tile``,
``moe_expected_rows`` in the totals.
A linear-attention layer (``models/transformer.py:GatedDeltaNet``) adds which
implementation of the delta rule's chunk-local stage its shapes chose
(``ops/gated_delta.py:stage_plan``): :func:`record_gdn_path`,
:func:`gdn_paths`, and ``gdn_kernel_calls``, ``gdn_jnp_calls`` in the totals;
a Kimi Delta Attention layer the same for the channel-wise rule
(:func:`record_kda_path`, :func:`kda_paths`, ``kda_kernel_calls``,
``kda_jnp_calls``).  A windowed attention layer adds what its three flash
calls cost, from shapes alone (``ops/attention.py:band_tiles``: the blocks,
the grid steps, the tiles computed beside the tiles the band needs and the
tiles a causal call would compute): :func:`record_window_call`,
:func:`window_calls`, and ``swa_calls``, ``swa_computed_tiles``,
``swa_needed_tiles``, ``swa_causal_tiles`` (a head a row, summed over the
traced layers) in the totals.  Every flash call of a layer adds which
backward its shapes chose (``ops/attention.py:bwd_plan``: one kernel, or
the pair where the row's dq would not fit the kernel's VMEM):
:func:`record_flash_bwd_path`, :func:`flash_bwd_paths`, and
``flash_bwd_fused_calls``, ``flash_bwd_pair_calls`` in the totals.

**The collector's pauses.**  The same call registers one callback on
``gc.callbacks``: each collection of the Python collector, in any thread,
becomes one :class:`GcPause` (a :class:`CompileRow` with its generation
beside it) on the same ``time.perf_counter`` clock, covering ``[at - value,
at]``, and a ``jax.profiler.TraceAnnotation("gc", generation=...)`` around
it, so that a capture holds the pause on its host plane beside the device
ops it held up.  The rows go to a ring of their own, :data:`GC_RING` rows
(the newest), returned by :func:`gc_pauses` and never by
:func:`compile_account`; :func:`compile_totals` carries running totals that
never roll out: ``gc_collections_gen<g>``, ``gc_pause_s_gen<g>`` and
``gc_longest_pause_s``.  A collection costs two clock reads, one annotation
and one append more than it did.
"""

from __future__ import annotations

import collections
import gc
import os
import pathlib
import time
from typing import NamedTuple

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

# jax's event -> its key in compile_totals().  The backend event spans jax's
# whole compile_or_get_cached call, so on a cache hit it holds the retrieval
# seconds too: the two are never added.
ACCOUNT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile_lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile_cache_retrieval_s",
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}


class CompileRow(NamedTuple):
    event: str              # a key of ACCOUNT_EVENTS
    fun_name: str | None    # jit(<fun_name>), where jax gives it
    at: float               # time.perf_counter() when the event arrived
    value: float            # seconds, or 1 for a count


_ROWS: list[CompileRow] = []
_PLANS: list = []       # models.remat_plan.RematPlan, one a traced step
_EXPERT_BUFFERS: list = []      # one a held-experts layer a traced step
_GDN_PATHS: list = []           # one a call of the delta rule a traced step
_KDA_PATHS: list = []           # one a call of the channel-wise rule
_WINDOW_CALLS: list = []        # one a windowed attention layer
_FLASH_BWD_PATHS: list = []     # one a flash call a traced step
_listening = False


class GcPause(NamedTuple):
    """One collection of the Python collector, in :class:`CompileRow`'s
    form (so :func:`covered_s` reduces it) with its generation."""
    event: str              # "gc"
    fun_name: str | None    # None
    at: float               # time.perf_counter() as the collection ended
    value: float            # its seconds, the callback's own included
    generation: int         # 0, 1 or 2 (2: a full collection)


# The Kimi-Linear step's set-up alone makes about 800 collections; a ring of
# 16,384 holds a benchmark run's whole set-up and window, and bounds an
# operator's run of days.
GC_RING = 16_384
_GC_PAUSES: collections.deque = collections.deque(maxlen=GC_RING)
_GC_TOTALS: dict = {}           # running totals, never rolled out
_gc_open = None                 # (start, annotation) of the collection under way


def _on_duration(event, seconds, fun_name=None, **_):
    if event in ACCOUNT_EVENTS:
        _ROWS.append(CompileRow(event, fun_name, time.perf_counter(),
                                float(seconds)))


def _on_count(event, **_):
    if event in ACCOUNT_EVENTS:
        _ROWS.append(CompileRow(event, None, time.perf_counter(), 1))


def _on_gc(phase, info):
    global _gc_open
    if phase == "start":
        start = time.perf_counter()
        note = jax.profiler.TraceAnnotation(
            "gc", generation=info["generation"])
        note.__enter__()
        _gc_open = (start, note)
    elif _gc_open is not None:
        start, note = _gc_open
        _gc_open = None
        note.__exit__(None, None, None)
        at = time.perf_counter()
        gen, seconds = info["generation"], at - start
        _GC_PAUSES.append(GcPause("gc", None, at, seconds, gen))
        for key, add in ((f"gc_collections_gen{gen}", 1),
                         (f"gc_pause_s_gen{gen}", seconds)):
            _GC_TOTALS[key] = _GC_TOTALS.get(key, 0) + add
        _GC_TOTALS["gc_longest_pause_s"] = max(
            _GC_TOTALS.get("gc_longest_pause_s", 0.0), seconds)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache and the compile account on;
    returns the cache directory used."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_count)
        gc.callbacks.append(_on_gc)
        _listening = True
    if ENV_VAR in os.environ:
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


def record_remat_plan(plan) -> None:
    """Keep the checkpoint plan a train step was just traced with."""
    _PLANS.append(plan)


def record_expert_buffer(fun_name: str, rows: int, row_tile: int,
                         expected_rows: float, first_rows: int) -> None:
    """Keep the shapes of the held experts' buffers a train step was just
    traced with (models/transformer.py:HeldExperts): the full buffer's
    rows, the row tile, the assignments expected under even routing, and
    the rows of the first buffer, the one a layer computes where its
    routing fits (equal to ``rows`` where there is one buffer)."""
    _EXPERT_BUFFERS.append({"fun_name": fun_name, "rows": rows,
                            "row_tile": row_tile,
                            "expected_rows": expected_rows,
                            "first_rows": first_rows})


def record_gdn_path(fun_name: str, path: str, chunk: int,
                    shapes: tuple) -> None:
    """Keep which implementation of the gated delta rule's chunk-local
    stage a train step was just traced with (ops/gated_delta.py): ``path``
    is ``"kernel"`` or ``"jnp"``, ``shapes`` the call's ``(rows, positions,
    key heads, value heads, key dim, value dim)``, ``chunk`` its length."""
    _GDN_PATHS.append({"fun_name": fun_name, "path": path,
                       "shapes": tuple(shapes), "chunk": chunk})


def gdn_paths() -> list:
    """The delta rule's calls so far, oldest first (a copy)."""
    return list(_GDN_PATHS)


def record_kda_path(fun_name: str, path: str, chunk: int,
                    shapes: tuple) -> None:
    """:func:`record_gdn_path` for the channel-wise rule
    (ops/gated_delta.py:kda_rule): ``shapes`` is the call's ``(rows,
    positions, heads, key dim, value dim)``."""
    _KDA_PATHS.append({"fun_name": fun_name, "path": path,
                       "shapes": tuple(shapes), "chunk": chunk})


def kda_paths() -> list:
    """The channel-wise rule's calls so far, oldest first (a copy)."""
    return list(_KDA_PATHS)


def record_window_call(fun_name: str, shapes: tuple, tiles: dict) -> None:
    """Keep what a windowed attention layer's flash calls cost in the train
    step that was just traced (models/transformer.py:Attention): ``shapes``
    is the call's ``(rows, heads, positions, head size)``, ``tiles`` the
    counts of ``ops/attention.py:band_tiles`` a head a row."""
    _WINDOW_CALLS.append(dict(tiles, fun_name=fun_name,
                              shapes=tuple(shapes)))


def record_flash_bwd_path(fun_name: str, path: str, shapes: tuple,
                          vmem_bytes: int) -> None:
    """Keep which backward a flash call of the train step that was just
    traced takes (models/transformer.py): ``path`` is ``"fused"`` or
    ``"pair"``, ``shapes`` the call's ``(rows, heads, positions, head size,
    value head size)``, ``vmem_bytes`` what the fused kernel would hold."""
    _FLASH_BWD_PATHS.append({"fun_name": fun_name, "path": path,
                             "shapes": tuple(shapes),
                             "vmem_bytes": vmem_bytes})


def flash_bwd_paths() -> list:
    """The flash calls so far, oldest first (a copy)."""
    return list(_FLASH_BWD_PATHS)


def window_calls() -> list:
    """The windowed layers so far, oldest first (a copy)."""
    return list(_WINDOW_CALLS)


def expert_buffers() -> list:
    """The expert buffers so far, oldest first (a copy)."""
    return list(_EXPERT_BUFFERS)


def remat_plans() -> list:
    """The plans so far, oldest first (a copy)."""
    return list(_PLANS)


def compile_account() -> list[CompileRow]:
    """The rows so far, oldest first (a copy)."""
    return list(_ROWS)


def gc_pauses() -> list[GcPause]:
    """The collector's pauses still in the ring, oldest first (a copy)."""
    return list(_GC_PAUSES)


def covered_s(rows) -> float:
    """Seconds covered by duration rows: the length of the union of their
    intervals ``[at - value, at]``.  A jitted function traced inside
    another reports a trace of its own within the outer one's, and counts
    once."""
    total, end = 0.0, float("-inf")
    for start, stop in sorted((r.at - r.value, r.at) for r in rows):
        if stop > end:
            total += stop - max(start, end)
            end = stop
    return total


def compile_totals() -> dict:
    """The account's totals under the keys of :data:`ACCOUNT_EVENTS`: the
    seconds covered by each kind of duration, and the counts; and of the
    newest checkpoint plan the blocks at each rung (rung 0 first), the bytes
    it keeps, its budget, the estimates it was chosen against and which
    instant set the first; the
    collector's collections and pause seconds by generation and its longest
    pause.  ``{}`` while the account is empty."""
    totals = {}
    if _ROWS:
        for event, key in ACCOUNT_EVENTS.items():
            mine = [r for r in _ROWS if r.event == event]
            totals[key] = (covered_s(mine) if key.endswith("_s")
                           else len(mine))
    if _PLANS:
        plan = _PLANS[-1]
        totals["remat_blocks_by_rung"] = [
            plan.rungs.count(r) for r in range(max(plan.rungs, default=0) + 1)]
        totals["remat_kept_bytes"] = plan.kept_bytes
        totals["remat_budget_bytes"] = plan.budget_bytes
        totals["remat_estimate_bytes"] = plan.estimate_bytes
        totals["remat_start_by"] = plan.start_by
        totals["remat_moe_out_blocks"] = sum(b > 0 for b in plan.moe_out)
        totals["remat_moe_out_bytes"] = sum(plan.moe_out)
        if plan.end_bytes is not None:
            totals["remat_end_bytes"] = plan.end_bytes
            totals["remat_walk_bytes"] = plan.walk_bytes
    if _EXPERT_BUFFERS:
        newest = _EXPERT_BUFFERS[-1]
        totals["moe_buffer_rows"] = newest["rows"]
        totals["moe_first_buffer_rows"] = newest["first_rows"]
        totals["moe_row_tile"] = newest["row_tile"]
        totals["moe_expected_rows"] = newest["expected_rows"]
    if _WINDOW_CALLS:
        totals["swa_calls"] = len(_WINDOW_CALLS)
        for key in ("computed_tiles", "needed_tiles", "causal_tiles"):
            totals[f"swa_{key}"] = sum(c[key] for c in _WINDOW_CALLS)
    if _FLASH_BWD_PATHS:
        for path in ("fused", "pair"):
            totals[f"flash_bwd_{path}_calls"] = sum(
                r["path"] == path for r in _FLASH_BWD_PATHS)
    for rule, rows in (("gdn", _GDN_PATHS), ("kda", _KDA_PATHS)):
        for path in sorted({r["path"] for r in rows}):
            totals[f"{rule}_{path}_calls"] = sum(
                r["path"] == path for r in rows)
    totals.update(_GC_TOTALS)
    return totals
