#!/usr/bin/env python
"""Causal language-model training (data-parallel) — beyond-reference capability.

The reference's largest model is a CNN over 32x32 images (SURVEY §5.7: no
sequence models anywhere); this example shows the framework's long-context
side on the same engine the image examples use: TransformerLM with the Pallas
flash-attention kernel, next-token loss, DP/DDP via the strategy layer, and
the standard checkpoint/metrics plumbing.

    python examples/train_lm.py --batch-size 32 --seq-len 128 --epochs 2
    python examples/train_lm.py --strategy ddp --coordinator h0:9999 \
        --num-processes 2 --process-id 0        # multi-host DDP
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax

from common import bootstrap
from dtdl_tpu.ckpt import save_weights
from dtdl_tpu.data import DataLoader, ShardedSampler, load_dataset
from dtdl_tpu.metrics import Reporter, StdoutSink
from dtdl_tpu.models import transformer_lm
from dtdl_tpu.obs import (GoodputMeter, Observer, lm_train_flops,
                          peak_flops_per_chip)
from dtdl_tpu.parallel import choose_strategy
from dtdl_tpu.train import init_state, make_lm_train_step
from dtdl_tpu.utils import seed_everything
from dtdl_tpu.utils.config import (add_ckpt_flags, add_data_flags,
                                   add_topology_flags, add_train_flags,
                                   flag, make_parser)


def main():
    parser = make_parser("dtdl_tpu: causal LM training (DP/DDP)")
    add_train_flags(parser, batch_size=32, lr=3e-4, epochs=2)
    add_data_flags(parser, dataset="synthetic_lm")
    add_ckpt_flags(parser)
    add_topology_flags(parser)
    flag(parser, "--strategy", default="auto",
         choices=["auto", "single", "dp", "ddp"])
    flag(parser, "--model-size", default="tiny",
         choices=["tiny", "small", "base", "large", "base-moe8"])
    flag(parser, "--seq-len", type=int, default=128)
    flag(parser, "--attn", default="flash", choices=["flash", "dense"])
    flag(parser, "--vocab-chunk-size", type=int, default=0,
         help=">0: vocab-chunked LM loss with tiles of N vocab COLUMNS "
              "(e.g. 2048) — the [B,S,V] logits are never materialized, "
              "so large-vocab models fit at long sequence")
    flag(parser, "--n-experts", type=int, default=0,
         help=">0: switch-MoE MLPs with this many experts (0 keeps the "
              "preset's own MLPs — 'base-moe8' is routed MoE already)")
    flag(parser, "--moe-dispatch", default="dense",
         choices=["dense", "routed"],
         help="MoE dispatch: dense one-hot oracle, or GShard-style "
              "capacity-factor top-k (the scale path — same flag surface "
              "as train_lm_4d.py)")
    flag(parser, "--capacity-factor", type=float, default=1.25,
         help="routed: per-expert slots = ceil(cf * seq * k / n_experts)")
    flag(parser, "--moe-top-k", type=int, default=1,
         help="routed: experts per token (1 = Switch, 2 = GShard top-2)")
    flag(parser, "--moe-group-size", type=int, default=0,
         help="routed: routing-group token cap (0 = 1024, the measured "
              "knee; capacity applies per group)")
    flag(parser, "--moe-aux-weight", type=float, default=0.01,
         help="Switch load-balance aux loss weight (added to the "
              "training loss; 0 disables)")
    flag(parser, "--generate-tokens", type=int, default=0,
         help=">0: after training, greedily decode this many tokens from "
              "a training-prefix prompt (KV-cache generate) and print "
              "them — an end-to-end check of the inference path")
    flag(parser, "--trace", default="",
         help="write a Chrome-trace-event JSON (Perfetto-loadable) of "
              "the host phases + settled device windows to this path")
    args = parser.parse_args()

    if args.dataset != "synthetic_lm":
        raise SystemExit("train_lm.py trains on token data; "
                         "use --dataset synthetic_lm")

    bootstrap(args)
    key = seed_everything(args.seed)
    strategy = choose_strategy(args.strategy)

    train_tokens, _ = load_dataset(args.dataset, seq_len=args.seq_len)
    # the MoE flags override the preset only when asked for, so the
    # 'base-moe8' preset keeps its own experts
    moe = dict(n_experts=args.n_experts, moe_dispatch=args.moe_dispatch,
               capacity_factor=args.capacity_factor,
               moe_top_k=args.moe_top_k,
               moe_group_size=args.moe_group_size) if args.n_experts else {}
    model = transformer_lm(args.model_size, max_seq=args.seq_len,
                           attn_impl=args.attn, **moe)
    if train_tokens.max() >= model.vocab_size:
        raise SystemExit("dataset vocab exceeds model vocab")

    nproc = jax.process_count()
    strategy.per_replica_batch(args.batch_size)   # validate divisibility
    sampler = ShardedSampler(len(train_tokens), nproc, jax.process_index(),
                             shuffle=True, seed=args.seed)
    loader = DataLoader({"tokens": train_tokens}, args.batch_size // nproc,
                        sampler=sampler)

    state = init_state(model, key,
                       jnp.zeros((1, args.seq_len), jnp.int32),
                       optax.adamw(args.lr))
    state = strategy.replicate(state)
    step = make_lm_train_step(strategy,
                              vocab_chunk_size=args.vocab_chunk_size,
                              moe_aux_weight=args.moe_aux_weight)

    # observability (dtdl_tpu.obs): goodput/MFU per log window through the
    # reporter, a recompile sentinel on the step, and — with --trace — a
    # Perfetto-loadable span trace of the host phases
    per_host_bs = args.batch_size // nproc
    # flops_per_step covers the whole per-host step (sharded over all
    # local devices), so the peak must be per-host too — per-chip peak
    # times local chips (GoodputMeter's denominator convention)
    peak = peak_flops_per_chip()
    obs = Observer(trace_path=args.trace or None, sentinel="warn",
                   goodput=GoodputMeter(
                       flops_per_step=lm_train_flops(model, per_host_bs,
                                                     args.seq_len),
                       tokens_per_step=per_host_bs * (args.seq_len - 1),
                       peak_flops=peak * jax.local_device_count()
                       if peak else None))
    step = obs.watch(step, "lm_train_step")
    global_step = 0
    import time as _time
    t_win, steps_win = _time.perf_counter(), 0
    with Reporter([StdoutSink()]) as reporter:
        for epoch in range(args.epochs):
            loader.set_epoch(epoch)
            for batch in loader:
                with obs.span("data"):
                    sharded = strategy.shard_batch(
                        {"tokens": jnp.asarray(batch["tokens"])})
                with obs.span("dispatch", step=global_step):
                    state, metrics = step(state, sharded)
                steps_win += 1
                if global_step % args.log_interval == 0:
                    with obs.span("drain"):
                        row = {"epoch": epoch, "step": global_step,
                               "loss": float(metrics["loss"]),
                               "accuracy": float(metrics["accuracy"]),
                               "ppl": float(np.exp(
                                   min(20.0, float(metrics["loss"]))))}
                        if "moe_aux_loss" in metrics:
                            row["moe_aux_loss"] = float(
                                metrics["moe_aux_loss"])
                    # the float() above settled the window: honest goodput
                    row.update(obs.window(steps_win,
                                          _time.perf_counter() - t_win))
                    t_win, steps_win = _time.perf_counter(), 0
                    reporter.report(row)
                global_step += 1
    if args.trace:
        print(f"trace written to {obs.save()}", flush=True)
    if args.save_model:
        path = save_weights(f"{args.out}/lm_final.msgpack", state.params)
        print(f"saved weights to {path}", flush=True)
    # diagnostic decode runs AFTER the save: a generation error (bad
    # flag combination, OOM) must never discard the trained weights
    if args.generate_tokens:
        from dtdl_tpu.models import generate
        if jax.process_count() == 1:
            # one prompt row per replica: the decode itself runs under
            # the training strategy (batch-sharded caches), like training
            n_rows = max(1, strategy.num_replicas)
            prompt = jnp.asarray(train_tokens[:n_rows, :8], jnp.int32)
            out = generate(model, state.params, prompt,
                           max_new_tokens=args.generate_tokens,
                           strategy=strategy)
        else:
            # multi-host: shard_batch would treat the prompt as this
            # host's contribution to a process-spanning global array
            # (batch x process_count vs the compiled cache shapes, and a
            # non-addressable output) — decode host-locally instead
            prompt = jnp.asarray(train_tokens[:1, :8], jnp.int32)
            out = generate(model, jax.device_get(state.params), prompt,
                           max_new_tokens=args.generate_tokens)
        print("generated:", np.asarray(out)[0].tolist(), flush=True)


if __name__ == "__main__":
    main()
