#!/usr/bin/env python
"""ResNet-50 DDP training — the north-star throughput workload.

BASELINE.json names "ResNet-50/ImageNet PyTorch DDP on v4-32 (SLURM ->
TPU-VM launcher)" among the configs to cover.  This script is that workload
TPU-native: ResNet-50 v1.5 in bfloat16 (float32 BN stats), data-parallel
over every chip in the mesh via shard_map + psum gradient sync, per-host
data sharding, SGD + cosine schedule with linear warmup, throughput
(samples/sec and samples/sec/chip) reported every log interval.

ImageNet itself isn't distributable with the repo; with no dataset present a
deterministic learnable synthetic set stands in at full 224x224x3 resolution
so the compute/communication profile is the real one.

    python examples/imagenet_resnet50.py --batch-size 256 --steps 100
    # multi-host (or zero-flag under SLURM; see launch/slurm.py):
    python examples/imagenet_resnet50.py --coordinator h0:8476 \
        --num-processes 4 --process-id $RANK --batch-size 1024
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from common import bootstrap, per_process_loader
from dtdl_tpu.data.synthetic import class_pattern_images
from dtdl_tpu.models import resnet50
from dtdl_tpu.parallel import choose_strategy
from dtdl_tpu.train import init_state, make_eval_step, make_train_step
from dtdl_tpu.utils import seed_everything
from dtdl_tpu.utils.config import (add_data_flags, add_topology_flags, flag,
                                   make_parser)


def main():
    parser = make_parser("dtdl_tpu: ResNet-50 DDP throughput workload")
    flag(parser, "-b", "--batch-size", type=int, default=256,
         help="GLOBAL batch size")
    flag(parser, "--steps", type=int, default=100)
    flag(parser, "--lr", type=float, default=0.1,
         help="base lr at batch 256 (scaled linearly with batch size)")
    flag(parser, "--warmup-steps", type=int, default=20)
    flag(parser, "--image-size", type=int, default=224)
    flag(parser, "--num-classes", type=int, default=1000)
    flag(parser, "--train-examples", type=int, default=4096,
         help="synthetic training pool size")
    flag(parser, "--log-interval", type=int, default=20)
    flag(parser, "--dtype", default="bfloat16",
         choices=["bfloat16", "float32"])
    flag(parser, "--s2d-stem", action="store_true",
         help="space-to-depth stem (faster on TPU; renames the stem param "
              "path, so snapshots are not interchangeable with the "
              "standard-stem tree)")
    flag(parser, "--seed", type=int, default=0)
    add_data_flags(parser, dataset="synthetic")
    add_topology_flags(parser)
    args = parser.parse_args()
    bootstrap(args)

    key = seed_everything(args.seed)
    strategy = choose_strategy("auto")
    n_chips = max(1, len(jax.devices()))

    model = resnet50(num_classes=args.num_classes,
                     dtype=jnp.bfloat16 if args.dtype == "bfloat16"
                     else jnp.float32,
                     s2d_stem=args.s2d_stem)
    base = args.lr * args.batch_size / 256  # linear scaling rule
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, base, args.warmup_steps, max(args.steps, args.warmup_steps + 1))
    tx = optax.chain(optax.add_decayed_weights(1e-4),
                     optax.sgd(schedule, momentum=0.9, nesterov=True))
    state = strategy.replicate(init_state(
        model, key, jnp.zeros((1, args.image_size, args.image_size, 3)), tx))
    train_step = make_train_step(strategy)

    x, y = class_pattern_images(args.train_examples,
                                (args.image_size, args.image_size, 3),
                                args.num_classes, seed=args.seed, noise=0.3)
    loader = per_process_loader(x, y, args.batch_size, shuffle=True,
                                seed=args.seed)

    step_i, t0, logged = 0, time.perf_counter(), 0
    epoch = 0
    while step_i < args.steps:
        loader.set_epoch(epoch)
        for batch in iter(loader):
            if step_i >= args.steps:
                break
            batch = strategy.shard_batch(batch)
            state, metrics = train_step(state, batch)
            step_i += 1
            if step_i % args.log_interval == 0 or step_i == args.steps:
                # dispatch is asynchronous: the value fetch waits for the
                # window's whole step chain before the clock is read
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                done = step_i - logged
                sps = args.batch_size * done / dt
                print(f"step {step_i}/{args.steps} "
                      f"loss {loss:.4f} "
                      f"acc {float(metrics['accuracy']):.4f} "
                      f"| {sps:,.0f} samples/sec "
                      f"({sps / n_chips:,.0f}/chip, {n_chips} chips) "
                      f"| {dt / done * 1e3:.1f} ms/step", flush=True)
                t0, logged = time.perf_counter(), step_i
        epoch += 1

    # quick sanity eval on the training pool (synthetic data is learnable)
    eval_step = make_eval_step(strategy)
    em = eval_step(state, strategy.shard_batch(
        {"image": jnp.asarray(x[: args.batch_size]),
         "label": jnp.asarray(y[: args.batch_size])}))
    print(f"final: train-pool acc "
          f"{float(em['correct_sum']) / float(em['count']):.4f}", flush=True)


if __name__ == "__main__":
    main()
