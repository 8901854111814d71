#!/usr/bin/env python
"""Single-device CIFAR-10 PyramidNet training — the baseline every
distributed variant mutates from.

Capability parity with reference pytorch/single_gpu.py:43-120: one device,
manual epoch/step loop, per-batch loss/acc/batch-time logging every 20 steps,
optional final state_dict save.  Differences by design: the step is one jitted
XLA program, ``--seed`` actually seeds (the reference parses and drops it,
pytorch/single_gpu.py:32-33), and the device is whatever JAX exposes (TPU
chip here, CPU elsewhere) instead of cuda:0.

    python examples/single_device.py --batch-size 64 --lr 0.1 --epochs 2
"""

import jax
import jax.numpy as jnp

from common import bootstrap, cifar_loaders, sgd_steplr
from dtdl_tpu.ckpt import save_weights
from dtdl_tpu.metrics import Reporter, StdoutSink
from dtdl_tpu.models import pyramidnet
from dtdl_tpu.parallel import SingleDevice
from dtdl_tpu.train import evaluate, init_state, make_eval_step, \
    make_train_step, train_epoch
from dtdl_tpu.utils import seed_everything
from dtdl_tpu.utils.config import (add_ckpt_flags, add_data_flags,
                                   add_train_flags, flag, make_parser)


def main():
    parser = make_parser("dtdl_tpu: single-device CIFAR-10 PyramidNet")
    add_train_flags(parser, batch_size=64, lr=0.1, epochs=20)
    add_data_flags(parser, dataset="cifar10")
    add_ckpt_flags(parser)
    flag(parser, "--gpu-nums", type=int, default=1,
         help="accepted for parity with the reference; must be 1 here")
    flag(parser, "--dtype", default="bfloat16",
         choices=["float32", "bfloat16"])
    args = parser.parse_args()
    if args.gpu_nums != 1:
        # reference guard: single_gpu.py:44-45 refuses gpu_nums != 1
        raise SystemExit("single_device.py trains on exactly one device; "
                         "use data_parallel.py / distributed_data_parallel.py")

    bootstrap(args)
    key = seed_everything(args.seed)
    strategy = SingleDevice()
    train_loader, val_loader = cifar_loaders(args, args.seed)
    tx, _ = sgd_steplr(args.lr, args.momentum, args.weight_decay,
                       len(train_loader))
    model = pyramidnet(dtype=jnp.dtype(args.dtype))
    state = init_state(model, key, jnp.zeros((1, 32, 32, 3)), tx)
    state = strategy.replicate(state)

    step = make_train_step(strategy)
    eval_step = make_eval_step(strategy)
    reporter = Reporter([StdoutSink()])
    for epoch in range(args.epochs):
        state, _ = train_epoch(step, state, train_loader, strategy,
                               reporter=reporter, epoch=epoch,
                               log_interval=args.log_interval)
        evaluate(eval_step, state, val_loader, strategy,
                 reporter=reporter, epoch=epoch)
    if args.save_model:
        path = save_weights(f"{args.out}/pyramidnet_final.msgpack",
                            state.params)
        print(f"saved weights to {path}", flush=True)


if __name__ == "__main__":
    main()
