"""The Python collector's pauses, as the program keeps them, and how the
``host`` readers reduce them.

``dtdl_tpu/runtime/compile_cache.py`` (since PR 37) registers one callback
on ``gc.callbacks`` in the same call that starts its compile account, and
keeps one row a collection in a ring of its own (``gc_pauses()``): the
account's row form, ``row.at`` (the ``time.perf_counter`` clock of the
benchmark's spans and of ``record["window"]``), ``row.value`` (seconds), so a
row covers ``[at - value, at]``, and ``row.generation``.  Seconds are the
union of the rows' intervals clipped to the interval asked for, as
``covered_s`` reduces the compile rows, so a pause counts once.  A program
that keeps no such rows (the parent of PR 37) gives nothing to read, and
``None`` is returned; where it keeps them and none falls in the interval
the reading is ``0.0``.
"""

from __future__ import annotations


def pauses(whole: bool = False):
    """The program's pause rows, oldest first, or None where the program
    keeps none (no accessor, or a ring no collection has reached).  With
    ``whole``, None too where the ring is full: its oldest rows may have
    rolled out, and a reading from the process's start would fall short."""
    try:
        from dtdl_tpu.runtime import compile_cache
        rows = compile_cache.gc_pauses()
        ring = compile_cache.GC_RING
    except (ImportError, AttributeError):
        return None
    if whole and len(rows) >= ring:
        return None
    return rows or None


def clipped(rows, lo: float, hi: float):
    """The rows that overlap ``[lo, hi]``, each cut to it (``at`` and
    ``value`` made the cut interval's end and length)."""
    out = []
    for r in rows:
        start, stop = max(r.at - r.value, lo), min(r.at, hi)
        if stop > start:
            out.append(r._replace(at=stop, value=stop - start))
    return out


def covered(rows, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that the rows cover, each instant once: the
    account's own ``covered_s`` of the cut rows."""
    from dtdl_tpu.runtime.compile_cache import covered_s
    return covered_s(clipped(rows, lo, hi))


def window(record):
    """``(start, end, steps)`` of the measured window, or None."""
    w = record.get("window") or {}
    if w.get("start") is None or w.get("seconds") is None:
        return None
    return w["start"], w["start"] + w["seconds"], w.get("steps") or 0
