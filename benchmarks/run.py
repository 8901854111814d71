#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves names to files and prints the contract's last line; it holds no
branch for any cell, configuration, runner or metric:

* the cell       -> ``BENCHMARK.json`` ``workloads`` + ``workloads/<cell>.json``
* its traffic    -> ``traffic/<traffic>.json`` (parameters of the one generator)
* its config     -> ``configs/<config>.json``, and from that file its family
  -> ``families/<model_type>.py`` beside ``configs/`` (``lib/modules.py``)
* its runner     -> ``runners/<runner>.py`` (``run(cell, cfg, opts)``)
* each per-layer metric that lists the cell -> ``metrics/<name>.py``
  (``read(record)`` returns a number, or None when there is nothing to read)

No chip is an error.  ``--rehearse`` swaps in the stand-in that the
configuration's file names (``"rehearsal"``, a file beside it) on the CPU
platform (as many virtual devices as the cell has chips), says so
in its output, and is never the default: a rehearsal's numbers are not
device numbers.
"""

import time
T_START = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]

from lib import modules  # noqa: E402


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    return modules.load_file(os.path.join(HERE, kind, name + ".py"), kind)


def load_config(path):
    """A configuration's file as it is run, with where its family's file
    is: ``families/<model_type>.py`` beside the file's ``configs/``."""
    cfg = load_json(path)
    cfg["family"] = os.path.join(os.path.dirname(os.path.dirname(path)),
                                 "families", cfg["model_type"] + ".py")
    return cfg


def resolve(manifest, workload, rehearse, data=HERE):
    """(cell, cfg): the cell's file over its traffic mix's, and the
    configuration's file as it is run.  ``data`` holds ``workloads/`` and
    ``traffic/``."""
    entries = [w for w in manifest["workloads"] if w["name"] == workload]
    if len(entries) != 1:
        raise SystemExit(f"workload {workload!r} is not in the manifest "
                         f"({[w['name'] for w in manifest['workloads']]})")
    entry = entries[0]
    cell = load_json(os.path.join(data, "workloads", entry["name"] + ".json"))
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"{workload}: {key!r} differs between the "
                             f"manifest and the cell's file")
    traffic = load_json(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    cell = dict(traffic, **cell, name=entry["name"])
    config = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    if len(config) != 1:
        raise SystemExit(f"config {cell['config']!r} is not in the manifest")
    path = os.path.join(ROOT, config[0]["file"])
    cfg = load_config(path)
    if rehearse:
        cfg = load_config(os.path.join(os.path.dirname(path),
                                       cfg["rehearsal"] + ".json"))
        cell.update(cfg.pop("cell"))
    return cell, cfg


def load_cell(workload, rehearse=False,
              manifest=os.path.join(ROOT, "BENCHMARK.json"), data=HERE):
    """``(manifest, cell, cfg)`` for ``run.py`` and the tools alike.  With
    ``rehearse`` it also puts JAX on the CPU platform with as many virtual
    devices as the cell has chips, so call it before anything else touches
    JAX's backend."""
    manifest = load_json(manifest)
    cell, cfg = resolve(manifest, workload, rehearse, data)
    if rehearse:
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", int(cell["chips"]))
    return manifest, cell, cfg


def applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny configuration on the CPU platform, for tests")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="for tests: another manifest of the same form")
    ap.add_argument("--data", default=HERE,
                    help="for tests: another directory of workloads/ and "
                         "traffic/ files")
    args = ap.parse_args(argv)

    manifest, cell, cfg = load_cell(args.workload, args.rehearse,
                                    args.manifest, args.data)
    runner = load_module("runners", cell["runner"])
    record = runner.run(cell, cfg, {
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "rehearse": args.rehearse, "t_start": T_START,
        "scratch": os.path.join(HERE, ".scratch", args.workload),
    })

    metrics, breakdown = {}, None
    if args.trace:
        for m in manifest["per_layer"]:
            if applies(m, args.workload):
                value = load_module("metrics", m["name"]).read(record)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = record.get("breakdown")
    else:
        for m in manifest["end_to_end"]:
            if applies(m, args.workload):
                metrics[m["name"]] = {
                    "value": record["end_to_end"][m["name"]],
                    "unit": m["unit"]}

    line = {"correct": record["correct"], "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics,
            "device": record["device"]}
    if breakdown:
        line["breakdown"] = breakdown
    line["rehearsal"] = args.rehearse
    # where set-up and the time after the window went (the driver reads
    # neither): the runner's spans, and the reference's seconds
    line["notes"] = record.get("notes", {})
    line["compared"] = record["compared"]
    if args.rehearse:
        print(f"REHEARSAL on {record['device']['platform']} x"
              f"{record['device']['count']}: not device numbers", flush=True)
    for name, row in record["compared"].items():
        print(f"compared {name}: {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    print(f"correct: {record['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
