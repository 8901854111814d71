#!/usr/bin/env python
"""chip_smoke.py — does the system still start on the chip?

One process, no children, no benchmark: drives the repo's main path once
through the entry points a user calls, at the full width of the widest
preset (``transformer_lm('large')``: d_model 1024, 16 layers, 8 heads x
128, d_ff 2816, vocab 32000, bf16, flash attention), with random weights
from a seed, and checks what comes out.

* trainer — ``choose_strategy('auto')`` -> ``init_state`` ->
  ``make_lm_train_step`` on seeded ``synthetic_lm`` tokens (seq 2048, 4
  sequences per chip): finite, falling losses from a program that holds
  the Mosaic flash kernels (``tpu_custom_call``), DDP over every chip
  when there is more than one;
* server — ``InferenceEngine(n_slots=8, page_size=16)`` behind
  ``Scheduler.run`` on seeded greedy requests (some speculative), with a
  bf16 and then an int8 KV pool: every request completes through the
  Pallas paged-attention kernel, one decode program, one prefill program
  per touched bucket, no recompiles;
* kernels — ``flash_attention`` (forward and grads, plain and fused
  rope) and ``paged_attention`` (decode and verify widths, bf16 and int8
  pools) against float32 references, within tolerances measured on a
  TPU v5 lite and written beside each check;
* four chips (when ``jax.device_count() >= 4``) — three steps of the 4D
  ``parallel/megatron.py`` engine at the same width on
  ``build_4d_mesh()``.

Any failed check raises; nothing is caught.  With no arguments the
platform must be ``tpu``: on anything else the script says what it found
and exits 2 before doing any work.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, with the device as JAX
reports it.  Timings printed on the way are labelled "smoke, not a
benchmark" and are not performance numbers.

``--rehearse`` walks the same phases at ``'tiny'`` width on the CPU
platform (Pallas interpreter) to debug the script itself before spending
chip time.  It says nothing about the chip and is never the default.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time

import numpy as np

# Kernel-parity tolerances: max|got - want| / max|want| against a float32
# reference fed the same bf16 inputs.  Each is about three times the
# worst error seen on a TPU v5 lite (PR 21 chip run, seed 0, the CHIP
# plan's shapes) — all of them bf16 rounding, 2^-8 = 3.9e-3 of the
# output's magnitude, not algorithmic differences.
TOL_FLASH_FWD = 1.5e-2     # seen: plain 3.12e-3, fused rope 4.06e-3
TOL_FLASH_GRAD = 2e-2      # seen: plain 2.98e-3, fused rope 6.02e-3
TOL_PAGED_BF16 = 1.5e-2    # seen: S=1 2.34e-3, S=5 3.78e-3
TOL_PAGED_INT8 = 2e-2      # seen: S=1 3.63e-3, S=5 6.05e-3


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one run.  Width is the preset's; these only set how much
    traffic goes through it."""
    platform: str            # the platform this plan is valid on
    size: str                # transformer_lm preset
    seq: int                 # training sequence length
    per_chip_batch: int
    train_steps: int
    n_slots: int
    prompt_lens: tuple       # one request each; buckets are powers of two
    spec_every: int          # every n-th request speculates (k=4)
    parity_seq: int          # flash parity sequence length
    parity_head_dim: int
    parity_paged: tuple      # (heads, pages per slot) of the paged parity
    mega_microbatches: int
    mega_batch: int

    @property
    def mosaic(self) -> bool:
        """Pallas kernels compile through Mosaic (else: interpreter)."""
        return self.platform == "tpu"


CHIP = Plan(platform="tpu", size="large", seq=2048, per_chip_batch=4,
            train_steps=6, n_slots=8,
            prompt_lens=(20, 28, 40, 52, 60, 75, 90, 100), spec_every=3,
            parity_seq=2048, parity_head_dim=128, parity_paged=(8, 16),
            mega_microbatches=4, mega_batch=8)
REHEARSAL = Plan(platform="cpu", size="tiny", seq=64, per_chip_batch=2,
                 train_steps=6, n_slots=2, prompt_lens=(5, 12),
                 spec_every=2, parity_seq=128, parity_head_dim=16,
                 parity_paged=(2, 4), mega_microbatches=2, mega_batch=4)

PAGE_SIZE = 16
SEED = 0


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def on_every_device(tree, devices) -> bool:
    """Every leaf of ``tree`` has an addressable shard on every device."""
    import jax
    want = set(devices)
    return all({s.device for s in leaf.addressable_shards} == want
               for leaf in jax.tree.leaves(tree))


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(bool(np.all(np.isfinite(got))), "kernel output is finite")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def train_phase(plan: Plan):
    """A few DP/DDP steps of the flax LM; returns (model, trained params)."""
    import jax
    import jax.numpy as jnp
    import optax

    from dtdl_tpu.data import load_dataset
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.parallel import choose_strategy
    from dtdl_tpu.runtime.compile_cache import remat_plans
    from dtdl_tpu.train import init_state, make_lm_train_step

    strategy = choose_strategy("auto")
    n_rep = strategy.num_replicas
    check(n_rep == jax.device_count(),
          f"strategy spans every device ({n_rep} replicas, "
          f"{jax.device_count()} devices)")
    model = transformer_lm(plan.size, dtype=jnp.bfloat16, attn_impl="flash")
    batch = plan.per_chip_batch * n_rep
    # seq + 1 tokens per row: the shifted inputs and targets both span
    # seq.  ONE batch, stepped on repeatedly: the loss must then fall,
    # which a handful of fresh batches cannot promise at every width.
    tokens, _ = load_dataset("synthetic_lm", seq_len=plan.seq + 1,
                             n_train=batch, n_test=1)
    check(int(tokens.max()) < model.vocab_size, "dataset fits the vocab")

    state = strategy.replicate(init_state(
        model, jax.random.PRNGKey(SEED),
        jnp.zeros((1, plan.seq), jnp.int32), optax.adamw(3e-4)))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    if n_rep > 1:
        check(on_every_device(state.params, jax.devices()),
              "replicated params have a shard on every device")
    step = make_lm_train_step(strategy)

    def shard():
        return strategy.shard_batch({"tokens": jnp.asarray(tokens)})

    t0 = time.perf_counter()
    lowered = step.lower(state, shard())
    t1 = time.perf_counter()
    compiled = lowered.compile()     # the part the compile cache serves
    lower_s, compile_s = t1 - t0, time.perf_counter() - t1
    check(("tpu_custom_call" in compiled.as_text()) == plan.mosaic,
          f"train step {'holds' if plan.mosaic else 'has no'} Mosaic "
          f"kernels (tpu_custom_call)")

    losses, step_s = [], []
    for _ in range(plan.train_steps):
        b = shard()
        t0 = time.perf_counter()
        state, metrics = compiled(state, b)
        jax.block_until_ready((state, metrics))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    check(all(math.isfinite(x) for x in losses), f"losses finite: {losses}")
    check(losses[-1] < losses[0], f"loss fell: {losses}")
    print(f"train: {plan.size} {n_params / 1e6:.0f}M params, "
          f"{type(strategy).__name__} x{n_rep}, batch {batch} x seq "
          f"{plan.seq}; losses {[round(x, 4) for x in losses]}")
    print(f"train: trace+lower {lower_s:.1f} s, compile {compile_s:.1f} "
          f"s, steady step {float(np.median(step_s[1:])):.3f} s (median "
          f"of {len(step_s) - 1}) — smoke, not a benchmark")
    if model.remat:     # 'large'; the rehearsal's 'tiny' recomputes nothing
        kept = remat_plans()[-1]
        check(len(kept.rungs) == model.n_layers and
              kept.kept_bytes <= kept.budget_bytes,
              f"the step's checkpoint plan covers every block within its "
              f"budget: {kept}")
        print(f"train: remat keeps {kept.kept_bytes / 1e9:.3f} GB a chip "
              f"(rung of each block {list(kept.rungs)}; budget "
              f"{kept.budget_bytes / 1e9:.3f} GB = limit {kept.limit_bytes} "
              f"less margin less an estimate of "
              f"{kept.estimate_bytes / 1e9:.3f} GB under rung 0 as the "
              f"backward pass begins"
              + ("" if kept.end_bytes is None else
                 f"; {kept.end_bytes / 1e9:.3f} GB as it ends, "
                 f"{kept.walk_bytes / 1e9:.3f} GB at the most between")
              + ")")
    return model, state.params


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def make_requests(plan: Plan, vocab: int):
    from dtdl_tpu.serve import Request

    rng = np.random.default_rng(SEED)
    reqs = []
    for i, n in enumerate(plan.prompt_lens):
        new = 16 + 4 * (i % 5)                       # 16..32 new tokens
        if i % plan.spec_every == plan.spec_every - 1:
            # a periodic prompt, so the n-gram draft has something to
            # propose and the verify program family compiles
            prompt = np.resize(rng.integers(0, vocab, 5), n)
            reqs.append(Request(prompt.tolist(), new, speculate=4))
        else:
            reqs.append(Request(rng.integers(0, vocab, n).tolist(), new))
    return reqs


def serve_phase(plan: Plan, model, params, kv_dtype):
    import jax

    from dtdl_tpu.obs import Observer
    from dtdl_tpu.serve import InferenceEngine, Scheduler

    obs = Observer(sentinel="raise")
    engine = InferenceEngine(model, params, n_slots=plan.n_slots,
                             page_size=PAGE_SIZE, kv_dtype=kv_dtype,
                             observer=obs)
    reqs = make_requests(plan, model.vocab_size)
    t0 = time.perf_counter()
    Scheduler(engine, seed=SEED, observer=obs).run(reqs)
    wall_s = time.perf_counter() - t0

    for r in reqs:
        check(r.done and r.error is None
              and len(r.tokens) == r.max_new_tokens,
              f"request {r.rid} completed (done={r.done}, "
              f"error={r.error!r}, {len(r.tokens)}/{r.max_new_tokens} "
              f"tokens)")
        check(all(0 <= t < model.vocab_size for t in r.tokens),
              f"request {r.rid} tokens inside the vocab")
    stats = engine.compile_stats()
    check(stats["kernels"]["paged_attention"]["enabled"] is plan.mosaic,
          f"paged_kernel='auto' resolved to "
          f"{'the Pallas kernel' if plan.mosaic else 'gather'}: "
          f"{stats['kernels']['paged_attention']}")
    buckets = sorted({engine.bucket_for(n) for n in plan.prompt_lens})
    check(stats["decode"] == 1, f"one decode program: {stats['decode']}")
    check(stats["prefill"] == {b: 1 for b in buckets},
          f"one prefill program per touched bucket {buckets}: "
          f"{stats['prefill']}")
    check(bool(stats["verify"]) and set(stats["verify"].values()) == {1},
          f"verify family compiled once per width: {stats['verify']}")
    check(obs.sentinel.summary()["recompile_events"] == 0,
          f"no recompiles: {obs.sentinel.summary()}")
    kv = stats["quant"]["kv_dtype"] or "bf16"
    print(f"serve[{kv} pool] on {jax.devices()[0]}: {len(reqs)} requests, "
          f"{sum(len(r.tokens) for r in reqs)} tokens, prefill buckets "
          f"{buckets}, verify widths {sorted(stats['verify'])}, paged "
          f"kernel {stats['kernels']['paged_attention']['enabled']}; "
          f"wall {wall_s:.1f} s with compiles — smoke, not a benchmark")


# ---------------------------------------------------------------------------
# kernel parity (op level, small shapes)
# ---------------------------------------------------------------------------

def flash_parity(plan: Plan):
    import jax
    import jax.numpy as jnp

    from dtdl_tpu.ops.attention import flash_attention, mha_reference
    from dtdl_tpu.ops.rope import apply_rope, rope_frequencies

    s, d = plan.parity_seq, plan.parity_head_dim
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, w = (jax.random.normal(kk, (1, 4, s, d), jnp.bfloat16)
                  for kk in keys)
    cos, sin = rope_frequencies(d, s)

    def kernel(q, k, v, rope):
        o = flash_attention(q, k, v, causal=True, rope=rope)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def reference(q, k, v, rope):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        if rope is not None:
            q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        o = mha_reference(q, k, v, causal=True)
        return jnp.sum(o * w), o

    for name, rope in (("plain", None), ("rope", (cos, sin))):
        run = lambda f: jax.jit(jax.value_and_grad(
            lambda q, k, v: f(q, k, v, rope), argnums=(0, 1, 2),
            has_aux=True))(q, k, v)
        (_, o), grads = run(kernel)
        (_, o_ref), grads_ref = run(reference)
        e_fwd = rel_err(o, o_ref)
        e_grad = max(rel_err(g, r) for g, r in zip(grads, grads_ref))
        print(f"parity flash[{name}] seq {s} head_dim {d}: fwd "
              f"{e_fwd:.2e} (tol {TOL_FLASH_FWD:.1e}), grads "
              f"{e_grad:.2e} (tol {TOL_FLASH_GRAD:.1e})")
        check(e_fwd < TOL_FLASH_FWD and e_grad < TOL_FLASH_GRAD,
              f"flash[{name}] within tolerance")


def paged_parity(plan: Plan):
    import jax
    import jax.numpy as jnp

    from dtdl_tpu.ops.paged_attention import paged_attention
    from dtdl_tpu.quant import kv_quantize

    b, d, (h, n_ptab) = 4, plan.parity_head_dim, plan.parity_paged
    n_pages, cap = b * n_ptab + 1, n_ptab * PAGE_SIZE
    rng = np.random.default_rng(SEED)
    table = jnp.asarray(
        1 + rng.permutation(b * n_ptab).reshape(b, n_ptab), jnp.int32)
    active = jnp.asarray([1, 1, 1, 0], jnp.int32)
    kp, vp, kq = jax.random.split(jax.random.PRNGKey(SEED), 3)
    pool_k = jax.random.normal(kp, (n_pages, h, PAGE_SIZE, d), jnp.bfloat16)
    pool_v = jax.random.normal(vp, (n_pages, h, PAGE_SIZE, d), jnp.bfloat16)
    scale = 1.0 / math.sqrt(d)

    def reference(q, pk, pv, pos):
        """float32 attention over the gathered, dequantized logical view."""
        view = lambda p: jnp.take(p, table, axis=0).transpose(
            0, 2, 1, 3, 4).reshape(b, h, cap, d)
        s_new = q.shape[2]
        logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            view(pk)) * scale
        cols = jnp.arange(cap)[None, None, None, :]
        qpos = (pos[:, None, None, None]
                + jnp.arange(s_new)[None, None, :, None])
        probs = jax.nn.softmax(jnp.where(cols <= qpos, logits, -1e30), -1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, view(pv))
        return jnp.where(active[:, None, None, None] > 0, out, 0.0)

    for kv, tol in (("bf16", TOL_PAGED_BF16), ("int8", TOL_PAGED_INT8)):
        if kv == "int8":
            k8, ks = kv_quantize(pool_k, dtype=jnp.int8)
            v8, vs = kv_quantize(pool_v, dtype=jnp.int8)
            pools, scales = (k8, v8), dict(key_scale=ks, value_scale=vs)
            deq = (k8.astype(jnp.float32) * ks[..., None],
                   v8.astype(jnp.float32) * vs[..., None])
        else:
            pools, scales = (pool_k, pool_v), {}
            deq = (pool_k.astype(jnp.float32), pool_v.astype(jnp.float32))
        for s_new in (1, 5):
            q = jax.random.normal(kq, (b, h, s_new, d), jnp.bfloat16)
            pos = jnp.asarray([5, cap // 2 + 3, cap - s_new, 40],
                              jnp.int32)
            got = jax.jit(lambda q, pk, pv: paged_attention(
                q, pk, pv, table, pos, active, scale=scale,
                **scales))(q, *pools)
            err = rel_err(got, jax.jit(reference)(q, *deq, pos))
            print(f"parity paged[{kv} pool] S={s_new} head_dim {d}: "
                  f"{err:.2e} (tol {tol:.1e})")
            check(err < tol, f"paged[{kv}] S={s_new} within tolerance")


# ---------------------------------------------------------------------------
# four chips: the 4D engine
# ---------------------------------------------------------------------------

def megatron_phase(plan: Plan, model):
    import jax
    import optax

    from dtdl_tpu.data import load_dataset
    from dtdl_tpu.parallel import megatron as M

    mesh = M.build_4d_mesh()
    shape = dict(mesh.shape)
    cfg = M.MegatronConfig(
        vocab_size=model.vocab_size, d_model=model.d_model,
        n_heads=model.n_heads, d_ff=model.d_ff, n_stages=shape["pipe"],
        layers_per_stage=model.n_layers // shape["pipe"],
        n_microbatches=plan.mega_microbatches, max_seq=plan.seq)
    opt = optax.adamw(3e-4)
    params = M.place_params(
        mesh, cfg, M.init_params(cfg, jax.random.PRNGKey(SEED)))
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    steps, bsz = 3, plan.mega_batch
    toks, _ = load_dataset("synthetic_lm", seq_len=plan.seq + 1,
                           n_train=bsz, n_test=1)
    losses = []
    t0 = time.perf_counter()
    for _ in range(steps):           # one batch, as in train_phase
        batch = M.shard_lm_batch(mesh, {
            "tokens": toks[:, :-1].astype(np.int32),
            "targets": toks[:, 1:].astype(np.int32),
            "mask": np.ones((bsz, plan.seq), np.float32)})
        params, opt_state, loss, _ = step(
            params, opt_state, batch["tokens"], batch["targets"],
            batch["mask"])
        losses.append(float(jax.block_until_ready(loss)))
    wall_s = time.perf_counter() - t0
    check(all(math.isfinite(x) for x in losses), f"4D losses finite: "
          f"{losses}")
    check(losses[-1] < losses[0], f"4D loss fell: {losses}")
    check(on_every_device((params, opt_state), mesh.devices.flat),
          "4D params and optimizer state have shards on every device")
    print(f"megatron: mesh {shape}, {cfg.n_stages} stages x "
          f"{cfg.layers_per_stage} layers, {cfg.n_microbatches} "
          f"microbatches, batch {bsz} x seq {plan.seq}; losses "
          f"{[round(x, 4) for x in losses]}; {steps} steps in "
          f"{wall_s:.1f} s with the compile — smoke, not a benchmark")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="'tiny' width on the CPU platform under the "
                         "Pallas interpreter; debugs this script, says "
                         "nothing about the chip")
    args = ap.parse_args(argv)
    plan = REHEARSAL if args.rehearse else CHIP

    import jax

    from dtdl_tpu.runtime.compile_cache import (compile_totals,
                                                enable_compile_cache)

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={device['platform']} "
          f"kind={device['kind']!r} count={device['count']} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)
    if dev.platform != plan.platform:
        print(f"chip_smoke: this run needs JAX platform "
              f"{plan.platform!r} but JAX found {dev.platform!r} "
              f"({dev.device_kind!r} x{device['count']}); nothing was "
              f"run", file=sys.stderr)
        return 2
    if args.rehearse:
        print("REHEARSAL — says nothing about the chip", flush=True)

    import flax.linen as nn

    from dtdl_tpu.obs import peak_flops_per_chip

    peak = peak_flops_per_chip()        # an unknown accelerator raises
    print(f"peak bf16 (table): "
          f"{'none on cpu' if peak is None else f'{peak / 1e12:.0f} TFLOP/s'}")

    model, params = train_phase(plan)
    # serve what was just trained, on one chip: the first device
    params = jax.device_put(nn.unbox(params), jax.devices()[0])
    serve_phase(plan, model, params, None)
    serve_phase(plan, model, params, "int8")
    del params
    flash_parity(plan)
    paged_parity(plan)
    if device["count"] >= 4:
        megatron_phase(plan, model)

    # where the start went: jax's own account of every trace, lowering,
    # compile and cache look-up of this process (smoke, not a benchmark)
    print("compile account: " + ", ".join(
        f"{k.removeprefix('compile_')} "
        f"{format(v, '.1f') if isinstance(v, float) else v}"
        for k, v in compile_totals().items()), flush=True)
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
