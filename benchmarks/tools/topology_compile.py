#!/usr/bin/env python3
"""Compile a cell's timed step for a v5e that is not attached, and print
what it needs of the chip's memory.  No chip time, nothing runs.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/topology_compile.py \\
        --workload olmo1b-train-b4s2048 [--layers 8 6 4] [--reference]

For each depth: ``memory_analysis()`` of the program the window drives
(``make_lm_train_step`` on the runner's own plan), as arguments + outputs -
aliases + temporaries, and whether the compiled text holds the Mosaic
kernels.  ``--reference`` also compiles the plain reference's step (the
f32 ``lib/reference.py`` with its AdamW update) so that its memory is known
before a chip run.  The bytes in each configuration file's ``memory`` come
from here.

The default backend here is the CPU, so ``ops/attention.py:_use_interpret``
would take the interpreter branch; this script steers it (here, not in the
program) and refuses a compiled step without ``tpu_custom_call``.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


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
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import dtdl_tpu.ops.attention as attention
    from dtdl_tpu.parallel.strategy import SingleDevice
    from dtdl_tpu.train import make_lm_train_step

    import run as harness
    from lib import correct
    from runners import train

    attention._use_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)

    _, cell, cfg = harness.load_cell(args.workload)
    if cell["chips"] != 1:
        raise SystemExit("this script lowers the one-chip step; a mesh "
                         "needs NamedShardings on topo.devices")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    rows, row_tokens = cell["batch_per_chip"], cell["row_tokens"]
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((rows, row_tokens),
                                                    jnp.int32)})
    out = {"workload": args.workload, "topology": args.topology,
           "device_kind": topo.devices[0].device_kind, "depths": {}}
    for layers in args.layers or [cfg["num_hidden_layers"]]:
        c = dict(cfg, num_hidden_layers=layers)
        plan = train.make_plan(cell, c)
        state = on_chip(jax.eval_shape(plan.build, jax.random.PRNGKey(0)))
        step = make_lm_train_step(
            SingleDevice(), vocab_chunk_size=int(cell["vocab_chunk_size"]))
        t0 = time.perf_counter()
        try:
            compiled = step.lower(state, batch).compile()
        except Exception as e:       # the compiler's refusal is the answer
            out["depths"][layers] = {"refused": str(e).splitlines()[0][:300]}
            print(json.dumps({layers: out["depths"][layers]}), flush=True)
            continue
        text = compiled.as_text()
        if "tpu_custom_call" not in text:
            raise SystemExit("no tpu_custom_call in the compiled step: the "
                             "interpreter branch was lowered")
        row = {"parameters": sum(
            int(jnp.prod(jnp.array(s))) for s in plan.shapes.values()),
            "step": _bytes(compiled),
            "mosaic_calls": text.count("tpu_custom_call"),
            "compile_s": round(time.perf_counter() - t0, 1)}
        if args.reference:
            params = on_chip({p: jax.ShapeDtypeStruct(s, jnp.float32)
                              for p, s in plan.shapes.items()})
            toks = on_chip(jax.ShapeDtypeStruct((rows, row_tokens), jnp.int32))
            ref_step = correct.reference_step(
                c, float(cell["optimizer"]["lr"]))
            t0 = time.perf_counter()
            rc = jax.jit(ref_step, donate_argnums=(0, 1, 2)).lower(
                params, params, params, toks,
                on_chip(jax.ShapeDtypeStruct((), jnp.float32))).compile()
            row["reference_step"] = _bytes(rc)
            row["reference_compile_s"] = round(time.perf_counter() - t0, 1)
        out["depths"][layers] = row
        print(json.dumps({layers: row}), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
