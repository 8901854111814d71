"""The all-reduces of a step in a device trace, and what they need of the
interconnect.

**How they are named.**  Under ``shard_map`` DDP the program's gradient
mean (``parallel/strategy.py:grad_sync``, ``lax.pmean`` under the scope
``grad_sync``, ``train/step.py``) compiles to ``all-reduce`` instructions
(v5e compiler, jax 0.9.0; my trace of ``olmo1b-train-ddp4``, PR 27).  XLA
names one it left alone after the jax primitive, ``%psum_invariant.<n>``,
and one its combiner merged from several leaves ``%all-reduce.<n>``; an
``XLA Ops`` event reads ``%psum_invariant.610 = f32[2048,8192]{...}
all-reduce(f32[2048,8192]{...} %bitcast_convert_fusion.1), channel_id=1,
replica_groups=...``.  The pattern is anchored like
``lib/program_names.py``'s: the instruction's name, whole, and the
opcode.  Where the compiler makes an all-reduce asynchronous, the pair is
``%all-reduce-start.<n>`` and ``%all-reduce-done.<n>`` (the done event
names its start as its operand), and what counts is from the start's
beginning to the done's end: the transfer is in flight all that while.
The step's other all-reduces (the loss mask's count and the metrics'
mean, scalars) match too; they are microseconds.
"""

from __future__ import annotations

import re

from . import xplane

_NAME = r"(?:psum_invariant|all-reduce)(?:\.\d+)?"
ALL_REDUCE_EVENT = re.compile(rf"^%{_NAME} = .*? all-reduce\(")
ALL_REDUCE_START = re.compile(
    r"^%(all-reduce-start(?:\.\d+)?) = .*? all-reduce-start\(")
ALL_REDUCE_DONE = re.compile(
    r"^%all-reduce-done(?:\.\d+)? = .*? all-reduce-done\(.*"
    r"%(all-reduce-start(?:\.\d+)?)\)")


def all_reduce_intervals(events) -> list:
    """``[(name, start_ns, duration_ns)]`` of one chip's all-reduces among
    its op events: a synchronous one as it is, an asynchronous pair as one
    interval from the start's beginning to the done's end."""
    out, open_starts = [], {}
    for name, start, duration in events:
        if ALL_REDUCE_EVENT.search(name):
            out.append((name, start, duration))
            continue
        began = ALL_REDUCE_START.search(name)
        if began:
            open_starts[began.group(1)] = start
            continue
        done = ALL_REDUCE_DONE.search(name)
        if done and done.group(1) in open_starts:
            t0 = open_starts.pop(done.group(1))
            out.append((name, t0, start + duration - t0))
    return out


def all_reduce_ms_per_step(trace):
    """Device milliseconds a traced step in which an all-reduce was running
    or in flight (the union of their intervals), averaged over the chips;
    None where there is nothing to read."""
    if not trace or not trace.get("devices") or not trace.get("steps"):
        return None
    per_chip = [xplane.busy_union_ns(all_reduce_intervals(ev))
                for ev in trace["devices"].values()]
    ns = sum(per_chip) / len(per_chip)
    return ns / 1e6 / trace["steps"] if ns > 0 else None


def ring_all_reduce_bytes(n_bytes: float, chips: int) -> float:
    """Bytes each chip has to send to all-reduce ``n_bytes`` among
    ``chips``: 2 (n - 1) / n of them (reduce-scatter, then all-gather),
    whatever the algorithm; nothing on one chip."""
    return 2.0 * (chips - 1) / chips * n_bytes
