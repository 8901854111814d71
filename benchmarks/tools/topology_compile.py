#!/usr/bin/env python3
"""Compile a cell's timed step for a v5e that is not attached, and print
what it needs of the chip's memory.  No chip time, nothing runs.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/topology_compile.py \\
        --workload olmo1b-train-b4s2048 [--layers 8 6 4] [--reference]

For each depth: ``memory_analysis()`` of the program the window drives
(``make_lm_train_step`` on the runner's own plan, under the strategy the
cell's ``chips`` give it: ``SingleDevice`` on one described chip,
``shard_map`` DDP on a mesh of four), as arguments + outputs - aliases +
temporaries on each device, the checkpoint plan the step traced with, the
collectives the compiler put in, and whether the compiled text holds the
Mosaic kernels.  ``--reference`` also compiles the plain reference's step (the
f32 ``lib/reference.py`` with its AdamW update) so that its memory is known
before a chip run.  The bytes in each configuration file's ``memory`` come
from here.  The compiled step's text goes to ``chiprun_out/hlo.<cell>.<depth>.txt``.

The default backend here is the CPU, so ``ops/attention.py:_use_interpret``
would take the interpreter branch and ``models/remat_plan.py`` would find
no ``bytes_limit`` to plan against; this script steers both (here, not in
the program: the limit is the one a v5e reports, PERF.md section 4) and
refuses a compiled step without ``tpu_custom_call``.
"""

import argparse
import collections
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

V5E_BYTES_LIMIT = 16_909_336_064     # memory_stats()["bytes_limit"], v5e
_COLLECTIVE = re.compile(
    r"^\s*%?[\w.\-]+ = .*? (all-reduce(?:-start)?|all-gather(?:-start)?|"
    r"reduce-scatter|all-to-all|collective-permute(?:-start)?)\(", re.M)


def _bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "aliases": m.alias_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "generated_code": m.generated_code_size_in_bytes,
            "total": total}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, nargs="*", default=None,
                    help="depths to try instead of the file's")
    ap.add_argument("--reference", action="store_true")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import dtdl_tpu.ops.attention as attention
    from dtdl_tpu.models import remat_plan
    from dtdl_tpu.parallel.strategy import DataParallel, SingleDevice
    from dtdl_tpu.runtime import compile_cache
    from dtdl_tpu.runtime.mesh import batch_sharded, build_mesh, replicated
    from dtdl_tpu.train import make_lm_train_step

    import run as harness
    from lib import correct
    from runners import train

    attention._use_interpret = lambda: False
    remat_plan.device_bytes_limit = lambda: V5E_BYTES_LIMIT >> 26 << 26
    jax.config.update("jax_enable_compilation_cache", False)

    _, cell, cfg = harness.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    chips = int(cell["chips"])
    one = SingleDeviceSharding(topo.devices[0])
    if chips == 1:          # what choose_strategy("auto") gives the cell
        strategy, whole, split = SingleDevice(), one, one
    else:
        mesh = build_mesh(devices=topo.devices[:chips])
        strategy = DataParallel(mesh)
        whole, split = replicated(mesh), batch_sharded(mesh)

    def placed(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    rows = cell["batch_per_chip"] * chips
    row_tokens = cell["row_tokens"]
    batch = placed({"tokens": jax.ShapeDtypeStruct((rows, row_tokens),
                                                   jnp.int32)}, split)
    out = {"workload": args.workload, "topology": args.topology,
           "device_kind": topo.devices[0].device_kind, "depths": {}}
    for layers in args.layers or [cfg["num_hidden_layers"]]:
        c = dict(cfg, num_hidden_layers=layers)
        plan = train.make_plan(cell, c)
        state = placed(jax.eval_shape(plan.build, jax.random.PRNGKey(0)),
                       whole)
        step = make_lm_train_step(
            strategy, vocab_chunk_size=int(cell["vocab_chunk_size"]))
        t0 = time.perf_counter()
        try:
            compiled = step.lower(state, batch).compile()
        except Exception as e:       # the compiler's refusal is the answer
            out["depths"][layers] = {"refused": str(e).splitlines()[0][:300]}
            print(json.dumps({layers: out["depths"][layers]}), flush=True)
            continue
        text = compiled.as_text()
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(
                args.out, f"hlo.{args.workload}.{layers}.txt"), "w") as f:
            f.write(text)       # how the compiler names what a trace shows
        if "tpu_custom_call" not in text:
            raise SystemExit("no tpu_custom_call in the compiled step: the "
                             "interpreter branch was lowered")
        row = {"parameters": sum(
            int(jnp.prod(jnp.array(s))) for s in plan.shapes.values()),
            "step": _bytes(compiled),
            "remat_plan": compile_cache.remat_plans()[-1]._asdict(),
            "collectives": dict(sorted(
                collections.Counter(_COLLECTIVE.findall(text)).items())),
            "mosaic_calls": text.count("tpu_custom_call"),
            "compile_s": round(time.perf_counter() - t0, 1)}
        if args.reference:
            params = placed({p: jax.ShapeDtypeStruct(s, jnp.float32)
                             for p, s in plan.shapes.items()}, one)
            toks = placed(jax.ShapeDtypeStruct((rows, row_tokens), jnp.int32),
                          one)
            ref_step = correct.reference_step(
                c, float(cell["optimizer"]["lr"]))
            t0 = time.perf_counter()
            rc = jax.jit(ref_step, donate_argnums=(0, 1, 2)).lower(
                params, params, params, toks,
                placed(jax.ShapeDtypeStruct((), jnp.float32), one)).compile()
            row["reference_step"] = _bytes(rc)
            row["reference_compile_s"] = round(time.perf_counter() - t0, 1)
        out["depths"][layers] = row
        print(json.dumps({layers: row}), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
