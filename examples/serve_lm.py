#!/usr/bin/env python
"""Continuous-batching LM serving — the request path of the north star.

The reference repos train and stop; this example closes the loop the
ROADMAP asks for ("serves heavy traffic"): a TransformerLM — freshly
initialized, restored from a training snapshot, or bridged from a
4D-megatron run — behind the dtdl_tpu.serve engine+scheduler.  Mixed
prompt lengths and mixed sampling configs share one fixed-shape decode
program; requests are admitted into KV-arena slots the moment one frees.

    python examples/serve_lm.py                       # synthetic traffic
    python examples/serve_lm.py --n-requests 32 --n-slots 8 \
        --temperature 0.8 --top-p 0.95
    python examples/serve_lm.py --restore ckpt.msgpack --model-size small
"""

import time

import numpy as np
import jax
import jax.numpy as jnp

from common import bootstrap
from dtdl_tpu.models import transformer_lm
from dtdl_tpu.serve import InferenceEngine, Request, SampleParams, Scheduler
from dtdl_tpu.utils import seed_everything
from dtdl_tpu.utils.config import flag, make_parser


def main():
    parser = make_parser("dtdl_tpu: batched LM serving")
    flag(parser, "--model-size", default="tiny",
         choices=["tiny", "small", "base", "large", "base-moe8"])
    flag(parser, "--restore", default="",
         help="msgpack weights to serve (default: random init)")
    flag(parser, "--n-slots", type=int, default=4,
         help="decode batch width (KV-arena rows)")
    flag(parser, "--n-requests", type=int, default=12)
    flag(parser, "--max-new-tokens", type=int, default=24)
    flag(parser, "--temperature", type=float, default=0.0,
         help="0 = greedy")
    flag(parser, "--top-k", type=int, default=0, help="0 = disabled")
    flag(parser, "--top-p", type=float, default=1.0, help="1 = disabled")
    flag(parser, "--harvest-lag", type=int, default=4,
         help="steps a sampled token may stay device-side before the "
              "host reads it (0 = sync every step)")
    flag(parser, "--speculate", type=int, default=0,
         help="speculative decoding: max drafted tokens per step "
              "(0 = off; lossless — greedy output is token-identical)")
    flag(parser, "--draft", default="ngram", choices=["ngram", "model"],
         help="draft source for --speculate: device-free n-gram prompt "
              "lookup, or a small draft transformer sharing the vocab")
    flag(parser, "--page-size", type=int, default=0,
         help="block-paged KV arena: tokens per page (0 = dense "
              "per-slot rows; must divide max_seq)")
    flag(parser, "--n-pages", type=int, default=0,
         help="page-pool size for --page-size (0 = dense-equivalent "
              "capacity; smaller overcommits HBM, admission then gates "
              "on free pages)")
    import argparse
    flag(parser, "--prefix-cache", action=argparse.BooleanOptionalAction,
         default=True,
         help="cross-request prefix caching over full prompt pages "
              "(paged arena only): identical prompt prefixes prefill "
              "once and are shared read-only")
    flag(parser, "--shared-prefix", type=int, default=0,
         help="synthetic traffic: give every request this many common "
              "leading tokens (a system prompt) so the prefix cache "
              "has something to hit")
    flag(parser, "--spill-host-mb", type=int, default=0,
         help="hierarchical KV cache (round 23): host-DRAM spill store "
              "byte budget in MiB (0 = off); evicted refcount-0 cached "
              "pages spill instead of freeing and a prefix miss "
              "restores them (needs --page-size + --prefix-cache)")
    flag(parser, "--spill-dir", default="",
         help="disk spill tier for --spill-host-mb: directory for the "
              "checksummed mmap'd spill file (host overflow demotes "
              "there; corrupt entries quarantine and recompute)")
    flag(parser, "--spill-disk-mb", type=int, default=256,
         help="disk spill file byte budget in MiB for --spill-dir")
    flag(parser, "--chunk-tokens", type=int, default=0,
         help="chunked prefill: per-step prompt token budget (0 = "
              "whole-prompt prefill); long admissions stop stalling "
              "in-flight decodes — greedy output stays token-identical")
    flag(parser, "--quantize", default="none",
         choices=["none", "w8", "w8kv8"],
         help="int8 serving (dtdl_tpu/quant): w8 = weight-only int8 "
              "matmuls, w8kv8 = + int8 KV arena; same compiled "
              "programs, ~4x less parameter HBM traffic")
    flag(parser, "--lora", default="",
         help="multi-tenant LoRA: comma-separated adapter checkpoint "
              "paths; requests round-robin over base + adapters, all "
              "batched through the SAME compiled steps (a missing path "
              "gets a random demo adapter saved there)")
    flag(parser, "--lora-rank", type=int, default=8,
         help="adapter rank for --lora (must match saved adapters)")
    flag(parser, "--json-schema", default="",
         help="grammar-constrained decoding: a JSON-schema file; every "
              "request's output is masked to valid JSON for it "
              "(vocab must cover ASCII, i.e. >= 128)")
    flag(parser, "--stream", action="store_true",
         help="attach a TokenStream per request and echo the first "
              "requests' tokens as the lag-harvest windows deliver them")
    flag(parser, "--seed", type=int, default=0)
    flag(parser, "--trace", default="",
         help="write a Chrome-trace-event JSON (Perfetto-loadable) of "
              "the scheduler phases (admit/dispatch/harvest) to this "
              "path")
    args = parser.parse_args()
    bootstrap(args)
    seed_everything(args.seed)

    model = transformer_lm(args.model_size, attn_impl="dense",
                           dtype=jnp.float32)
    example = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(args.seed), example)["params"]
    import flax.linen as nn
    params = nn.unbox(params)
    if args.restore:
        from dtdl_tpu.ckpt import load_weights
        params = load_weights(args.restore, params)

    lora_paths = [p for p in args.lora.split(",") if p]
    for p in lora_paths:
        # out-of-the-box demo: synthesize (and persist) an adapter for
        # any path that doesn't exist yet
        import os
        if not os.path.exists(p):
            from dtdl_tpu.ckpt import save_weights
            from dtdl_tpu.serve import adapter_template
            tpl = adapter_template(params, rank=args.lora_rank)
            arng = np.random.default_rng(hash(p) % (2 ** 31))
            save_weights(p, jax.tree_util.tree_map(
                lambda x: np.asarray(arng.normal(0, 0.02, x.shape),
                                     np.float32), tpl))
            print(f"  --lora: saved demo adapter to {p}")

    from dtdl_tpu.obs import Observer
    obs = Observer(trace_path=args.trace or None, sentinel="warn")
    engine = InferenceEngine(model, params, n_slots=args.n_slots,
                             observer=obs, page_size=args.page_size,
                             n_pages=args.n_pages or None,
                             quantize_weights=args.quantize != "none",
                             kv_dtype=("int8" if args.quantize == "w8kv8"
                                       else None),
                             lora_rank=(args.lora_rank if lora_paths
                                        else 0),
                             lora_adapters=(len(lora_paths) + 1
                                            if lora_paths else 0))
    draft = None
    if args.speculate and args.draft == "model":
        # demo draft transformer: a narrower random-init LM sharing the
        # vocab (real deployments restore trained draft weights)
        from dtdl_tpu.serve import ModelDraft
        dm = transformer_lm("tiny", vocab_size=model.vocab_size,
                            attn_impl="dense", dtype=jnp.float32)
        dp = nn.unbox(dm.init(jax.random.PRNGKey(args.seed + 1),
                              example)["params"])
        # warmup pre-compiles the (ctx-bucket, k-bucket) generate
        # family NOW so the first request doesn't eat the compile
        draft = ModelDraft(dm, dp, warmup=args.speculate)
    sched = Scheduler(engine, seed=args.seed,
                      harvest_lag=args.harvest_lag, observer=obs,
                      draft=draft, prefix_cache=args.prefix_cache,
                      chunk_tokens=args.chunk_tokens or None,
                      spill_host_bytes=args.spill_host_mb << 20 or None,
                      spill_dir=args.spill_dir or None,
                      spill_disk_bytes=(args.spill_disk_mb << 20
                                        if args.spill_dir else None))
    sp = SampleParams(temperature=args.temperature, top_k=args.top_k,
                      top_p=args.top_p)

    # synthetic traffic: mixed prompt lengths, one shared sampling
    # config; --shared-prefix prepends a common "system prompt" so the
    # paged arena's prefix cache has repeated leading pages to hit
    rng = np.random.default_rng(args.seed)
    hi = min(64, model.max_seq // 2)
    if not 0 <= args.shared_prefix <= model.max_seq - hi - 1:
        parser.error(f"--shared-prefix must be in [0, "
                     f"{model.max_seq - hi - 1}] for this model")
    common = rng.integers(0, model.vocab_size,
                          args.shared_prefix).tolist()
    lens = rng.integers(4, hi, args.n_requests)

    dfa = None
    eos = None
    if args.json_schema:
        import json as _json
        if model.vocab_size < 128:
            parser.error("--json-schema needs a vocab covering ASCII "
                         f"(>= 128); this model has {model.vocab_size}")
        from dtdl_tpu.serve import byte_vocab, compile_json_schema
        with open(args.json_schema) as f:
            schema = _json.load(f)
        eos = model.vocab_size - 1
        dfa = compile_json_schema(schema, byte_vocab(model.vocab_size),
                                  eos_id=eos)
        print(f"  --json-schema: {dfa.n_states}-state token DFA "
              f"({dfa.nbytes():,} bytes of masks)")

    def mk_stream(i):
        if not args.stream:
            return None
        from dtdl_tpu.serve import TokenStream
        if i >= 2:              # echo only the first requests
            return TokenStream()
        return TokenStream(callback=lambda new, i=i: print(
            f"    stream req {i}: +{new}"))

    # round-robin tenants: base, then each --lora adapter in turn
    tenants = [None] + lora_paths
    reqs = [Request(common + rng.integers(0, model.vocab_size,
                                          n).tolist(),
                    args.max_new_tokens, sampling=sp,
                    speculate=args.speculate,
                    adapter=tenants[i % len(tenants)],
                    grammar=dfa, eos_id=(eos if dfa is not None
                                         else None),
                    stream=mk_stream(i))
            for i, n in enumerate(lens)]

    t0 = time.perf_counter()
    sched.run(reqs)
    dt = time.perf_counter() - t0
    for r in reqs[:4]:
        print(f"  req {r.rid}: prompt[{len(r.prompt)}] -> "
              f"{r.tokens[:12]}{'...' if len(r.tokens) > 12 else ''}")
    s = sched.metrics.summary()
    print(f"served {s['requests_finished']} requests in {dt:.2f}s  "
          f"(decode {s['decode_tokens_per_sec']} tok/s, occupancy "
          f"{s['occupancy_mean']:.0%}, ttft {s['ttft_s_mean'] * 1e3:.1f}ms)")
    if "ttft_s_p50" in s:
        print(f"  ttft p50/p95/p99: {s['ttft_s_p50'] * 1e3:.1f} / "
              f"{s['ttft_s_p95'] * 1e3:.1f} / {s['ttft_s_p99'] * 1e3:.1f} ms"
              f"   per-token p50/p99: "
              f"{s.get('tok_latency_s_p50', 0.0) * 1e3:.2f} / "
              f"{s.get('tok_latency_s_p99', 0.0) * 1e3:.2f} ms")
    if args.page_size:
        # the paged-arena receipts: how much prefill the prefix cache
        # skipped, and how many pool pages live traffic ever pinned
        print(f"  paged kv (page_size={args.page_size}): prefix hit "
              f"rate {s['prefix_hit_rate']:.0%}  prefill tokens saved "
              f"{s['prefill_tokens_saved']}  pages in use "
              f"{s['pages_in_use_last']}/{s['page_capacity']} "
              f"(peak {s['pages_in_use_peak']})  shed "
              f"{s['requests_shed']}")
    if args.spill_host_mb:
        # the hierarchy receipts: pages that left HBM and came back
        # instead of being recomputed, split by the tier that hit
        print(f"  kv spill: spilled {s['pages_spilled']} pages "
              f"({s['spill_bytes'] >> 10} KiB)  restored "
              f"{s['pages_restored']} (host {s['spill_host_hits']} / "
              f"disk {s['spill_disk_hits']} hits, "
              f"{s['restore_s'] * 1e3:.1f}ms)  quarantined "
              f"{s['spill_quarantined']}")
    if args.quantize != "none":
        # the quantization receipts: decode bytes/token (the TPU
        # roofline numerator), KV capacity gained at fixed HBM, and the
        # measured logits drift of int8 rounding on a probe prompt
        q = engine.compile_stats()["quant"]
        ref = InferenceEngine(model, params, n_slots=args.n_slots,
                              page_size=args.page_size,
                              n_pages=args.n_pages or None)
        rq = ref.compile_stats()["quant"]
        kv_x = (rq["kv_arena_bytes"] / q["kv_arena_bytes"]
                if q["kv_arena_bytes"] else 1.0)
        probe = jnp.asarray([reqs[0].prompt], jnp.int32)
        lf = model.apply({"params": params}, probe)
        lq = engine.model.apply({"params": engine.params}, probe)
        drift = float(jnp.max(jnp.abs(lf - lq))) \
            / max(float(jnp.max(jnp.abs(lf))), 1e-9)
        print(f"  quantized ({args.quantize}): decode bytes/token "
              f"{q['decode_hbm_bytes_per_token']:,} (f32: "
              f"{rq['decode_hbm_bytes_per_token']:,})  param bytes "
              f"{q['param_bytes']:,} ({rq['param_bytes']:,} f32)  "
              f"kv capacity x{kv_x:.2f} at fixed HBM "
              f"(~{int(args.n_slots * kv_x)} slots for these "
              f"{args.n_slots})  probe logits drift {drift:.1%}")
    if args.speculate:
        # per-request ACCEPTED tokens/sec (delivered tokens over the
        # request's own decode window) — the user-visible spec win
        rates = sorted((len(r.tokens) - 1) / (r.t_done - r.t_first)
                       for r in reqs
                       if len(r.tokens) > 1 and r.t_done > r.t_first)
        pct = (lambda p: rates[min(len(rates) - 1,
                                   int(p * (len(rates) - 1)))]) \
            if rates else (lambda p: 0.0)
        print(f"  speculative k<={args.speculate} ({args.draft}): "
              f"acceptance {s['spec_acceptance_rate']:.0%}  "
              f"tokens/step {s['tokens_per_step_mean']:.2f}  "
              f"accepted-tok/s p50/p95: {pct(0.5):.1f} / {pct(0.95):.1f}  "
              f"draft overhead {s['draft_s'] * 1e3:.1f}ms")
    if lora_paths:
        # the multi-tenant receipts: per-adapter delivered tokens, all
        # through ONE decode program (adapter ids are data)
        by = s["tokens_by_adapter"]
        mix = "  ".join(f"{k.rsplit('/', 1)[-1]}={v}"
                        for k, v in sorted(by.items()))
        print(f"  multi-lora ({len(lora_paths)} adapters, rank "
              f"{args.lora_rank}): tokens by tenant: {mix}  bank loads "
              f"{engine.adapter_bank.n_loads} evictions "
              f"{engine.adapter_bank.n_evictions}")
    if dfa is not None:
        ok = sum(1 for r in reqs if r.error is None)
        print(f"  constrained ({args.json_schema}): {ok}/{len(reqs)} "
              f"requests completed valid JSON; illegal draft tokens "
              f"trimmed {s['grammar_rejected_tokens']}")
        for r in reqs[:2]:
            body = r.tokens[:-1] if r.tokens and r.tokens[-1] == eos \
                else r.tokens
            print(f"    req {r.rid}: "
                  f"{''.join(chr(t) for t in body)!r}")
    if args.stream:
        print(f"  streaming: {s['stream_deliveries']} incremental "
              f"deliveries across {len(reqs)} requests")
    print("compiled programs:", engine.compile_stats())
    if args.trace:
        print(f"trace written to {obs.save()}", flush=True)


if __name__ == "__main__":
    main()
