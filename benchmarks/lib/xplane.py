"""From the profiler's ``.xplane.pb`` to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  The reduction
works on plain tuples ``(name, start_ns, duration_ns)`` so that a test can
hand it a hand-built or recorded event list.

On a TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops``
holds one event per executed HLO op (fusions, custom calls, copies), and
``XLA Modules`` one per executed program.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, span_prefix: str = "bench:") -> dict:
    """``{"devices": {n: [(name, start, dur), ...]}, "spans": [...]}``.

    ``spans`` are the benchmark's own ``TraceAnnotation`` spans from the
    host plane (names starting with ``span_prefix``), on the trace's
    clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events
                          if e.name.startswith(span_prefix)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def clip(events, t0: float, t1: float):
    """Events cut to the window [t0, t1] (ns)."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for s, d in sorted((s, d) for _, s, d in events):
        if s > end:
            total += d
            end = s + d
        elif s + d > end:
            total += s + d - end
            end = s + d
    return total


def time_by_name(events) -> dict:
    out = {}
    for name, _, d in events:
        out[name] = out.get(name, 0.0) + d
    return out


def time_matching(events, pattern: str) -> float:
    """Summed duration of events whose name matches ``pattern``; nested
    events are not expected on the op line, so this is a plain sum."""
    rx = re.compile(pattern)
    return sum(d for name, _, d in events if rx.search(name))


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HEAD = re.compile(r"(\(.*?\)|\S+) ([\w\-]+)\(")
_PARAM = re.compile(r"%state_(?:params|opt_state_\d+)__(\w+?)___value")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def label(name: str) -> str:
    """A short label for an op event whose name is its HLO text: the
    instruction's name without its number, its result's shapes without
    layouts, a custom call's target, and the parameters it reads with
    ``block_<i>`` made ``block_N`` — so that the same op of every layer
    falls under one label."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:120]
    op = re.sub(r"\.\d+$", "", head.lstrip("%"))
    rest = _LAYOUT.sub("", rest)
    m = _HEAD.match(rest)
    bits = [op, m.group(1)[:70] if m else ""]
    target = _TARGET.search(rest)
    if target:
        bits.append(target.group(1))
    params = sorted({re.sub(r"block_\d+", "block_N", p)
                     for p in _PARAM.findall(rest)})
    if params:
        bits.append("<- " + ",".join(params)[:60])
    return " ".join(b for b in bits if b)


def idle_gaps(events, t0: float, t1: float):
    """``[(start, duration)]`` of the intervals of [t0, t1] in which no
    event runs, longest first."""
    gaps, end = [], t0
    for s, d in sorted((s, d) for _, s, d in clip(events, t0, t1)):
        if s > end:
            gaps.append((end, s - end))
        end = max(end, s + d)
    if t1 > end:
        gaps.append((end, t1 - end))
    return sorted(gaps, key=lambda g: -g[1])


def covering_span(spans, at: float) -> str:
    """Name of the innermost benchmark span that covers time ``at``."""
    best = None
    for name, s, d in spans:
        if s <= at <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no benchmark span (host between calls)"
