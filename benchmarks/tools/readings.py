#!/usr/bin/env python3
"""The readings a cell's limits are set from, many seeds in one process.

    python3 benchmarks/tools/readings.py --workload <cell> \\
        --seeds 101 102 ... --control-seeds 101 102 103 --fault-seeds 101 102 103

* each ``--seeds`` seed: the program's first steps through the
  runner's own ``Session`` (the timed path at the timed sizes), the plain
  reference on the same seed, and the numbers compared (lower readings);
* each ``--control-seeds`` seed: the reference in float8 put in the
  program's place (upper readings);
* each ``--fault-seeds`` seed: the reference with half of the batch left
  out put in the program's place and, for a cell across chips, with all
  but the first chip's rows left out, as when the gradients' exchange is
  (a state left unchanged reads 1 by the measure and needs no run).

One JSON object per reading on standard output, and all of them in
``chiprun_out/readings.<cell>.json``.  Not part of a benchmark run.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)

    import run as harness
    from lib import correct
    from runners import train

    _, cell, cfg = harness.load_cell(args.workload, args.rehearse)
    ses = train.Session(cell, cfg, {"rehearse": args.rehearse})
    rows, references = [], {}

    def emit(kind, seed, side, refr, **more):
        row = dict(kind=kind, seed=seed, **correct.numbers(side, refr), **more)
        print(json.dumps(row), flush=True)
        # the file also keeps both sides' norms of every leaf
        rows.append(dict(row, leaves={"side": side, "reference": refr}))

    def reference(seed):
        ses.seed = seed
        if seed not in references:
            references[seed] = ses.reference("f32")
        return references[seed]

    for seed in args.seeds:
        t0 = time.perf_counter()
        state = ses.start(seed)
        state, program = ses.first_steps(state)
        del state
        t1 = time.perf_counter()
        refr = reference(seed)
        emit("program", seed, program, refr,
             program_s=t1 - t0, reference_s=time.perf_counter() - t1)
    for seed in args.control_seeds:
        refr = reference(seed)
        t0 = time.perf_counter()
        emit(f"control:{args.control}", seed, ses.reference(args.control),
             refr, control_s=time.perf_counter() - t0)
    for seed in args.fault_seeds:
        refr = reference(seed)
        for fault in correct.faults_of(ses.chips):
            if fault != "state_unchanged":
                emit("fault:" + fault, seed,
                     ses.reference("f32", fault=fault), refr)

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"readings.{args.workload}.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    names = sorted(k for k, v in rows[0].items()
                   if k.endswith(("_gap", "_median")))
    for kind in sorted({r["kind"] for r in rows}):
        these = [r for r in rows if r["kind"] == kind]
        for n in names:
            vals = [r[n] for r in these]
            print(f"{kind:20s} {n:18s} n={len(vals)} "
                  f"min {min(vals):.3e} max {max(vals):.3e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
