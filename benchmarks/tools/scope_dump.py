#!/usr/bin/env python3
"""Device time by the program's own scopes, by hand: run a cell's traced
window (keeping the trace, as ``trace_dump.py`` does) and write to
``chiprun_out/scope_dump.<cell>.json``

* which stat of an ``XLA Ops`` event in the raw ``.xplane.pb`` carries the
  op's name stack (every stat key seen, with one value each), or that none
  does: then instruction names (``%fusion.337``) are mapped to name stacks
  through the ``metadata={op_name="..."}`` of ``compiled.as_text()``;
* the device milliseconds a step by ``(component, pass)`` through the
  program's own map (``dtdl_tpu.obs.trace.device_component``), the share
  left unattributed, and the largest unattributed ops with their stacks.
  A fusion carries one ``op_name``, its root's: one that spans two scopes
  is counted under its root's.

    python3 benchmarks/tools/scope_dump.py --workload <cell> --seed 7 --seconds 3

The metric readers cannot do this yet: ``runners/train.py`` deletes the
trace before they run and ``lib/xplane.py:load`` keeps an op's name, start
and duration only (PERF.md section 7 hands both to a ``benchmark`` issue).
Needs a program with the catalogue (PR 25 on).
"""

import argparse
import json
import os
import re
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def stacks_from_hlo(text: str) -> dict:
    """``{instruction name: op_name}`` of every instruction of an HLO
    module's text that carries ``metadata={op_name="..."}``."""
    out = {}
    for line in text.splitlines():
        head, name = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if head and name:
            out[head.group(1)] = name.group(1)
    return out


def instruction_of(event_name: str) -> str:
    """``fusion.337`` from an op event's name (its whole HLO text, or the
    bare instruction name)."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def looks_like_a_stack(value) -> bool:
    return isinstance(value, str) and "/" in value and (
        value.startswith(("jit(", "pjit(")) or "jvp(" in value)


def by_component(events, stack_of, steps: int, component_of):
    """``(rows, unattributed)``: device ms a step by ``(component, pass)``
    of ``events`` (``(name, start_ns, duration_ns)``, already cut to the
    window), largest first, and the events' time with no catalogued scope
    by event label, largest first.  ``stack_of(event name)`` gives the name
    stack or None."""
    totals, loose = {}, {}
    for name, _, ns in events:
        stack = stack_of(name)
        component, phase = component_of(stack) if stack else (None, None)
        key = (component or "unattributed",
               phase if component else "-")
        totals[key] = totals.get(key, 0.0) + ns
        if component is None:
            label = (instruction_of(name).rstrip("0123456789").rstrip("."),
                     stack or "no op_name")
            loose[label] = loose.get(label, 0.0) + ns
    ms = lambda ns: ns / 1e6 / max(1, steps)            # noqa: E731
    rows = [[c, p, ms(ns)] for (c, p), ns in
            sorted(totals.items(), key=lambda kv: -kv[1])]
    unattributed = [[op, stack, ms(ns)] for (op, stack), ns in
                    sorted(loose.items(), key=lambda kv: -kv[1])[:25]]
    return rows, unattributed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)

    import run as harness
    from lib import kernels, xplane
    from runners import train

    from dtdl_tpu.obs.trace import device_component

    _, cell, cfg = harness.load_cell(args.workload, args.rehearse)
    hlo = {}

    def keep_hlo(compiled, session):
        hlo["text"] = compiled.as_text()
        return compiled

    record = train.run(cell, cfg, {
        "seed": args.seed, "seconds": args.seconds, "trace": True,
        "rehearse": args.rehearse, "t_start": T_START, "keep_trace": True,
        "scratch": os.path.join(HERE, ".scratch", args.workload)},
        wrap_step=keep_hlo)

    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane.find_xplane(record["traced"]["dir"]))
    t0, t1 = record["trace"].get("t0"), record["trace"].get("t1")
    steps = record["traced"]["steps"]

    # -- which stat carries the name stack -----------------------------------
    stat_keys, stack_keys, stat_stacks, modules = {}, {}, {}, {}
    device_events = None
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    modules[e.name] = modules.get(e.name, 0) + 1
            if line.name != xplane.OP_LINE or device_events is not None:
                continue
            device_events = []
            for e in line.events:
                device_events.append(
                    (e.name, float(e.start_ns), float(e.duration_ns)))
                for key, value in e.stats:
                    stat_keys.setdefault(key, repr(value)[:160])
                    if looks_like_a_stack(value):
                        stack_keys[key] = stack_keys.get(key, 0) + 1
                        stat_stacks.setdefault(e.name, value)
    report = {"workload": args.workload, "steps": steps,
              "window_ns": [t0, t1], "xla_modules": modules,
              "op_event_stat_keys": stat_keys,
              "stat_keys_holding_a_name_stack": stack_keys}

    hlo_stacks = stacks_from_hlo(hlo.get("text", ""))
    report["hlo_instructions_with_op_name"] = len(hlo_stacks)
    if stack_keys:
        report["stack_source"] = "xplane stat " + max(
            stack_keys, key=stack_keys.get)
        stack_of = stat_stacks.get
    else:
        report["stack_source"] = ("compiled.as_text() metadata op_name, by "
                                  "instruction name (no stat of an XLA Ops "
                                  "event holds a name stack)")
        stack_of = lambda name: hlo_stacks.get(instruction_of(name))  # noqa: E731

    if device_events and t0 is not None:
        cut = xplane.clip(device_events, t0, t1)
        rows, loose = by_component(cut, stack_of, steps, device_component)
        busy = xplane.busy_union_ns(cut) / 1e6 / steps
        report["busy_ms_per_step"] = busy
        report["by_component_ms_per_step"] = rows
        report["unattributed_top"] = loose
        report["events_in_window"] = len(cut)
        report["events_without_stack"] = sum(
            1 for n, _, _ in cut if not stack_of(n))
        flash = {}
        for name, _, ns in cut:
            if re.search(kernels.FLASH_EVENT, name):
                key = instruction_of(name).rstrip("0123456789").rstrip(".")
                flash.setdefault(key, [0, 0.0])
                flash[key][0] += 1
                flash[key][1] += ns / 1e6 / steps
        report["flash_events_by_instruction"] = flash
    report["result"] = {k: record[k] for k in (
        "correct", "end_to_end", "window", "traced", "device", "notes")}

    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"scope_dump.{args.workload}.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"scope_dump {args.workload}: {steps} traced steps, stacks from "
          f"{report['stack_source']}")
    print("stat keys of an XLA Ops event:", json.dumps(stat_keys)[:1500])
    for component, phase, ms in report.get("by_component_ms_per_step", []):
        print(f"  {component:14s} {phase:10s} {ms:9.3f} ms/step "
              f"{100 * ms / report['busy_ms_per_step']:6.2f} %")
    for op, stack, ms in report.get("unattributed_top", [])[:12]:
        print(f"  unattributed {ms:8.3f} ms/step  {op}  <- {stack[:110]}")
    print("flash:", json.dumps(report.get("flash_events_by_instruction")))
    print("modules:", json.dumps(modules)[:400])
    return 0


if __name__ == "__main__":
    sys.exit(main())
