#!/usr/bin/env python3
"""Look at one trace by hand: run a cell's traced window and write what the
profiler recorded to ``chiprun_out/`` — every plane and line with its event
count, the device lines' time by event name (the whole HLO text: how a
Pallas kernel is named today), and the first two steps' events as a JSON
list from which ``tests/data/trace_events.*.json.gz`` was cut.

    python3 benchmarks/tools/trace_dump.py --workload <cell> --seed 7 --seconds 3
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)

    import run as harness
    from lib import xplane
    from runners import train

    _, cell, cfg = harness.load_cell(args.workload, args.rehearse)
    record = train.run(cell, cfg, {
        "seed": args.seed, "seconds": args.seconds, "trace": True,
        "rehearse": args.rehearse, "t_start": T_START, "keep_trace": True,
        "scratch": os.path.join(HERE, ".scratch", args.workload)})

    from jax.profiler import ProfileData
    path = xplane.find_xplane(record["traced"]["dir"])
    data = ProfileData.from_file(path)
    t0, t1 = record["trace"].get("t0"), record["trace"].get("t1")
    report = {"xplane_bytes": os.path.getsize(path), "planes": [],
              "window_ns": [t0, t1], "steps": record["traced"]["steps"]}
    kept = {}
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            row = {"line": line.name, "events": len(events)}
            if plane.name.startswith("/device:"):
                by_name = {}
                for e in events:
                    by_name.setdefault(e.name, [0, 0.0])
                    by_name[e.name][0] += 1
                    by_name[e.name][1] += e.duration_ns
                row["top"] = sorted(
                    ([n, c, d / 1e6] for n, (c, d) in by_name.items()),
                    key=lambda r: -r[2])[:60]
                if t0 is not None:
                    span = (t1 - t0) / max(1, record["traced"]["steps"])
                    kept[f"{plane.name}|{line.name}"] = [
                        [e.name, e.start_ns - t0, e.duration_ns]
                        for e in events
                        if t0 <= e.start_ns < t0 + 2 * span]
            lines.append(row)
        report["planes"].append({"plane": plane.name, "lines": lines})
    report["spans"] = [[n, s - (t0 or 0), d]
                       for n, s, d in record["trace"]["spans"]][:80]
    report["result"] = {k: record[k] for k in (
        "correct", "compared", "end_to_end", "window", "traced",
        "breakdown", "device", "memory_stats", "notes")}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"trace_dump.{args.workload}.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    with open(os.path.join(args.out, f"trace_events.{args.workload}.json"),
              "w") as f:
        json.dump(kept, f)
    print(json.dumps(report["result"])[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
