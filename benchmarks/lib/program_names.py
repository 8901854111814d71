"""What the program names of its own device work and keeps of its own
set-up, and how the readers find it.

**Kernel names.**  ``dtdl_tpu/ops/attention.py`` passes ``name=`` to its
three ``pallas_call``s (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``;
the rope and the plain variants share them).  On the chip (jax 0.9.0, my
trace of ``olmo1b-train-b4s2048``, PR 25) XLA takes the instruction's name
from it: an ``XLA Ops`` event reads ``%flash_fwd.<n> = ... custom-call(...),
custom_call_target="tpu_custom_call"``.  The patterns are anchored like
``lib/kernels.py:FLASH_EVENT`` (which matches all three, having ``flash`` in
the name): the instruction's name, whole, and the Mosaic target.  A program
without these names (the parent of PR 25: ``%attn.<n>``) matches none, and
the readers return ``None``.

**The compile account.**  ``dtdl_tpu/runtime/compile_cache.py`` keeps one
row for each of jax's compile events (``jax.monitoring``), in the process,
on the ``time.perf_counter`` clock of the benchmark's spans:
``row.event``, ``row.fun_name``, ``row.at`` (arrival), ``row.value``
(seconds, or 1 for a count).  The set-up readers take the rows that arrived
before ``record["window"]["start"]``, name the events by the keys of the
account's own totals (``ACCOUNT_EVENTS``: ``compile_trace_s`` ...), and
reduce them with the account's own ``covered_s``: a duration row covers
``[at - value, at]`` and seconds are the length of the union, because jax
reports a jitted function traced inside another within the outer one's
trace, and a cache retrieval within its ``backend_compile_duration``.  The
step's program is told from every other the process compiled (the
benchmark's own weights generator and readings, in a cell) by
``row.fun_name``: ``<name>`` or ``jit(<name>)`` for a name of
``dtdl_tpu/obs/trace.py:STEP_NAMES``.  A program that keeps no account (the
parent of PR 25) gives nothing to read.
"""

from . import xplane

DURATIONS = ("compile_trace_s", "compile_lower_s", "compile_backend_s",
             "compile_cache_retrieval_s")


def _kernel_event(name: str) -> str:
    return (rf'^%{name}(\.\d+)? = '
            r'.*custom_call_target="tpu_custom_call"')


FLASH_FWD_EVENT = _kernel_event("flash_fwd")
FLASH_BWD_DQ_EVENT = _kernel_event("flash_bwd_dq")
FLASH_BWD_DKV_EVENT = _kernel_event("flash_bwd_dkv")


def kernel_ms_per_step(trace, pattern: str):
    """Device milliseconds a traced step of the events matching ``pattern``
    (mean over the chips), or None where there is nothing to read."""
    if not trace or not trace.get("devices") or not trace.get("steps"):
        return None
    per_chip = [xplane.time_matching(ev, pattern)
                for ev in trace["devices"].values()]
    ns = sum(per_chip) / len(per_chip)
    return ns / 1e6 / trace["steps"] if ns > 0 else None


def _account():
    """The program's compile account (its module), or None where the
    program keeps none."""
    try:
        from dtdl_tpu.runtime import compile_cache
        compile_cache.compile_account, compile_cache.covered_s
    except (ImportError, AttributeError):
        return None
    return compile_cache


def setup_rows(record, keys):
    """The account's rows from before the measured window whose event
    totals under one of ``keys``, or None: no window start in the record,
    no account, or no such row."""
    start = (record.get("window") or {}).get("start")
    account = _account()
    if start is None or account is None:
        return None
    return [r for r in account.compile_account()
            if r.at < start and account.ACCOUNT_EVENTS[r.event] in keys
            ] or None


def _of_step(rows):
    from dtdl_tpu.obs.trace import STEP_NAMES
    return [r for r in rows if r.fun_name and
            r.fun_name.removeprefix("jit(").removesuffix(")") in STEP_NAMES]


def step_seconds(record, keys):
    """Seconds of set-up covered by the step's program's rows."""
    rows = setup_rows(record, keys)
    rows = rows and _of_step(rows)
    return _account().covered_s(rows) if rows else None


def other_seconds(record, keys):
    """Seconds of set-up covered by the rows and not by the step's
    program's: every other program the process traced and compiled."""
    rows = setup_rows(record, keys)
    if not rows:
        return None
    covered_s = _account().covered_s
    return covered_s(rows) - covered_s(_of_step(rows))
