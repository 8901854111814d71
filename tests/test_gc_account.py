"""The compile account's record of the Python collector (PR 37).

``enable_compile_cache`` registers one callback on ``gc.callbacks``; each
collection becomes one :class:`GcPause` row on the ``perf_counter`` clock in
a bounded ring of its own (``gc_pauses()``), never a row of
``compile_account()``, with running totals in ``compile_totals()`` and a
``gc`` annotation on the profiler's host plane.
"""

import collections
import contextlib
import gc
import time

import jax
import jax.numpy as jnp
import pytest

from dtdl_tpu.obs import Observer
from dtdl_tpu.runtime import compile_cache


@contextlib.contextmanager
def _collector_off():
    """No collection but the ones a test forces (each still runs the
    callbacks)."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


class _Clock:
    """A ``time`` stand-in whose ``perf_counter`` reads the given instants,
    then the real clock (a collection after the test's own is no error)."""

    def __init__(self, *instants):
        self.instants = list(instants)

    def perf_counter(self):
        return self.instants.pop(0) if self.instants else time.perf_counter()


def _fresh(monkeypatch, ring=compile_cache.GC_RING):
    monkeypatch.setattr(compile_cache, "_GC_PAUSES",
                        collections.deque(maxlen=ring))
    monkeypatch.setattr(compile_cache, "_GC_TOTALS", {})


def _pause(gen):
    compile_cache._on_gc("start", {"generation": gen})
    compile_cache._on_gc("stop", {"generation": gen})


def test_a_forced_full_collection_is_one_generation_2_row():
    compile_cache.enable_compile_cache()
    with _collector_off():
        totals = compile_cache.compile_totals()
        t0 = compile_cache.time.perf_counter()
        gc.collect(2)
        t1 = compile_cache.time.perf_counter()
    new = [r for r in compile_cache.gc_pauses() if r.at > t0]
    assert len(new) == 1, new
    row, = new
    assert isinstance(row, compile_cache.GcPause)
    assert (row.event, row.fun_name, row.generation) == ("gc", None, 2)
    assert t0 <= row.at - row.value < row.at <= t1
    after = compile_cache.compile_totals()
    assert after["gc_collections_gen2"] == totals.get(
        "gc_collections_gen2", 0) + 1
    assert after["gc_pause_s_gen2"] >= totals.get("gc_pause_s_gen2", 0) \
        + row.value - 1e-12
    assert after["gc_longest_pause_s"] >= row.value
    # the account's own reduction takes the row as it takes a compile row
    assert compile_cache.covered_s([row]) == pytest.approx(row.value)


def test_a_second_enable_registers_no_second_callback():
    compile_cache.enable_compile_cache()
    compile_cache.enable_compile_cache()
    assert gc.callbacks.count(compile_cache._on_gc) == 1


@_collector_off()
def test_the_ring_holds_its_bound_and_the_totals_never_roll_out(monkeypatch):
    assert compile_cache.GC_RING >= 16_384
    assert compile_cache._GC_PAUSES.maxlen == compile_cache.GC_RING
    ring, extra = 64, 5
    _fresh(monkeypatch, ring)
    n = ring + extra
    monkeypatch.setattr(compile_cache, "time", _Clock(*[
        t for i in range(n) for t in (10.0 * i, 10.0 * i + 1e-3 * (i + 1))]))
    for i in range(n):
        _pause(i % 3)
    rows = compile_cache.gc_pauses()
    assert len(rows) == ring
    # the oldest rolled out, the newest kept
    assert rows[0].at == pytest.approx(10.0 * extra + 1e-3 * (extra + 1))
    assert rows[-1].at == pytest.approx(10.0 * (n - 1) + 1e-3 * n)
    totals = compile_cache.compile_totals()
    assert sum(totals[f"gc_collections_gen{g}"] for g in range(3)) == n
    assert totals["gc_longest_pause_s"] == pytest.approx(1e-3 * n)


@_collector_off()
def test_compile_totals_carry_the_collector_exactly(monkeypatch):
    _fresh(monkeypatch)
    for name in ("_ROWS", "_PLANS", "_EXPERT_BUFFERS", "_GDN_PATHS",
                 "_KDA_PATHS", "_WINDOW_CALLS", "_FLASH_BWD_PATHS"):
        monkeypatch.setattr(compile_cache, name, [])
    assert compile_cache.compile_totals() == {}
    monkeypatch.setattr(compile_cache, "time", _Clock(
        1.0, 1.5, 2.0, 3.5, 4.0, 4.25))
    for gen in (0, 2, 0):
        _pause(gen)
    assert compile_cache.compile_totals() == {
        "gc_collections_gen0": 2, "gc_pause_s_gen0": pytest.approx(0.75),
        "gc_collections_gen2": 1, "gc_pause_s_gen2": pytest.approx(1.5),
        "gc_longest_pause_s": pytest.approx(1.5)}
    assert [(r.at, r.value, r.generation)
            for r in compile_cache.gc_pauses()] == [
        (1.5, pytest.approx(0.5), 0), (3.5, pytest.approx(1.5), 2),
        (4.25, pytest.approx(0.25), 0)]
    # a stop with no start (a callback added mid-collection) is no row
    compile_cache._on_gc("stop", {"generation": 1})
    assert len(compile_cache.gc_pauses()) == 3


def test_the_compile_account_holds_no_pause_row():
    compile_cache.enable_compile_cache()

    def collected_under(x):
        gc.collect()
        return x + 1.0

    jax.jit(collected_under)(jnp.arange(4.0)).block_until_ready()
    gc.collect(2)
    assert compile_cache.gc_pauses()
    rows = compile_cache.compile_account()
    assert rows and all(type(r) is compile_cache.CompileRow
                        and r.event in compile_cache.ACCOUNT_EVENTS
                        for r in rows)
    # Observer.summary() carries the collector's totals with the rest
    summary = Observer().summary()
    assert {"gc_collections_gen2", "gc_pause_s_gen2",
            "gc_longest_pause_s"} <= set(summary)


def test_a_capture_holds_each_pause_as_a_gc_annotation(tmp_path):
    from jax.profiler import ProfileData
    compile_cache.enable_compile_cache()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with _collector_off():
            gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    found = [dict(e.stats).get("generation")
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events if e.name == "gc"]
    assert 2 in found, found
