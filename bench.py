#!/usr/bin/env python
"""Benchmark: training throughput + MFU for the reference's headline models.

The reference's published numbers (reference pytorch/README.md:41-43,122-125,
128): PyramidNet-110 alpha=270, CIFAR-10, batch 64, Tesla P100 — 0.255 s/batch
= 251 samples/sec on one GPU.  This script times the same training step on
whatever device JAX exposes, plus the BASELINE.json north-star workload
(ResNet-50, ImageNet shapes), across a batch-size sweep, and computes MFU
from the compiled step's `cost_analysis()` FLOPs against the detected chip's
bf16 peak.

stdout carries exactly ONE JSON line (the driver contract), kept COMPACT —
round 4's line grew past the driver's tail-capture window and truncated
mid-record (BENCH_r04.json parsed:null), so the headline numbers had no
machine-readable artifact.  The final line now carries only scalars:

    {"metric": "...", "value": N, "unit": "samples/sec", "vs_baseline": N,
     "mfu": N, "resnet50_mfu": N, "lm_mfu": N, "lm_tokens_per_sec": N,
     "records_file": "bench_records.json"}

The full per-config records and the modeled scaling section are written to
``records_file`` (JSON) and echoed to stderr.  vs_baseline > 1.0 means
faster than the reference's single-P100 batch time.  Everything
human-readable (the per-config table, the reference-table comparison) also
goes to stderr.

Honest timing: warmup steps first (compile + autotune), then blocking timing
of a fixed sample budget with data already on device.  Dispatch is
asynchronous, so a VALUE FETCH ends the timed region: float() on the last
step's loss waits for the whole dependency chain exactly as
block_until_ready on it would — one scalar round-trip amortized over the
whole timed run.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import optax

BASELINE_SAMPLES_PER_SEC = 64 / 0.255  # reference pytorch/README.md:41 (P100)

# chip peaks + analytic LM FLOPs live in the obs subsystem now (PR 3);
# bench.py re-exports the old names so scripts/lm_sweep.py et al. keep
# importing `from bench import lm_analytic_flops, peak_flops_per_chip`
from dtdl_tpu.obs.goodput import (  # noqa: E402
    _PEAK_BF16, lm_train_flops, peak_flops_per_chip,
)

from dtdl_tpu.runtime.compile_cache import enable_compile_cache  # noqa: E402

lm_analytic_flops = lm_train_flops


def _flops_of(compiled) -> float | None:
    """Total FLOPs of one compiled step, from XLA's cost analysis."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    f = ca.get("flops")
    return float(f) if f else None


def bench_one(model_name: str, batch_size: int, warmup: int = 10,
              sample_budget: int | None = None) -> dict:
    """Time one (model, batch_size) config; returns the record row."""
    from dtdl_tpu.models import pyramidnet, resnet50
    from dtdl_tpu.parallel import choose_strategy
    from dtdl_tpu.train import init_state, make_train_step

    strategy = choose_strategy("auto")
    if model_name == "resnet50":
        model = resnet50(dtype=jnp.bfloat16, s2d_stem=True)
        shape, classes = (224, 224, 3), 1000
        sample_budget = sample_budget or 4096
    else:
        model = pyramidnet(dtype=jnp.bfloat16)
        shape, classes = (32, 32, 3), 10
        sample_budget = sample_budget or 9600
    iters = max(20, sample_budget // batch_size)

    tx = optax.sgd(0.1, momentum=0.9, nesterov=False)
    state = strategy.replicate(init_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1,) + shape), tx))
    step = make_train_step(strategy)

    rng = np.random.default_rng(0)
    # a handful of distinct on-device batches so no lucky caching occurs
    batches = [strategy.shard_batch({
        "image": jnp.asarray(rng.normal(size=(batch_size,) + shape),
                             jnp.float32),
        "label": jnp.asarray(rng.integers(0, classes, batch_size)),
    }) for _ in range(4)]

    compiled = step.lower(state, batches[0]).compile()
    flops_per_step = _flops_of(compiled)

    for i in range(warmup):
        state, metrics = compiled(state, batches[i % len(batches)])
    float(metrics["loss"])

    t0 = time.perf_counter()
    for i in range(iters):
        state, metrics = compiled(state, batches[i % len(batches)])
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"

    samples_per_sec = batch_size * iters / dt
    row = {
        "model": model_name,
        "batch_size": batch_size,
        "samples_per_sec": round(samples_per_sec, 2),
        "step_time_ms": round(1e3 * dt / iters, 3),
        "vs_baseline": round(samples_per_sec / BASELINE_SAMPLES_PER_SEC, 3),
    }
    peak = peak_flops_per_chip()
    if flops_per_step:
        # cost_analysis() reports the per-device (SPMD-partitioned) module's
        # FLOPs, so the denominator is the per-chip peak — not peak * n_chips
        achieved = flops_per_step * iters / dt
        row["flops_per_step"] = flops_per_step
        row["achieved_tflops"] = round(achieved / 1e12, 2)
        if peak:
            row["mfu"] = round(achieved / peak, 4)
    return row


def bench_lm(batch_size: int = 8, seq: int = 4096, size: str = "base",
             warmup: int = 5, iters: int = 30) -> dict:
    """Causal-LM train step (TransformerLM, Pallas flash attention, bf16)
    — the long-context workload (same configs as the README's tokens/sec
    table).  Reports tokens/sec + MFU.

    'large' (d_model 1024, 239M params) is the roofline-cash row
    (LM_ROOFLINE.md §5: "further MFU comes from model shape").  Its bench
    config was swept on the v5e (LM_ROOFLINE.md §6): **bs 4, no remat,
    dense head** wins at 0.583 MFU — at bs 4 the activations (~7 GB) and
    the [4, 4095, 32k] f32 logits (~2.1 GB) fit beside the AdamW state,
    and both remat (+1x fwd recompute) and the chunked head (backward
    re-does the logit matmuls) burn real FLOPs the analytic MFU numerator
    deliberately does not credit (remat'd bs8 = 0.419, chunked bs4 =
    0.560).  The preset
    keeps ``remat=True`` as the safe default for *user* workloads at
    bigger batch; the bench overrides it because the measurement exists
    to show what the hardware ceiling allows.

    ``mfu`` uses the analytic model-FLOP count (`lm_analytic_flops`);
    ``mfu_xla`` keeps the raw cost_analysis number, which understates the
    step because Pallas kernel FLOPs are invisible to it."""
    import optax as _optax
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.parallel import choose_strategy
    from dtdl_tpu.train import init_state, make_lm_train_step

    strategy = choose_strategy("auto")
    overrides = {"remat": False} if size == "large" else {}
    model = transformer_lm(size, max_seq=seq, **overrides)
    tx = _optax.adamw(3e-4)
    state = strategy.replicate(init_state(
        model, jax.random.PRNGKey(0),
        jnp.zeros((1, seq), jnp.int32), tx))
    step = make_lm_train_step(strategy)
    rng = np.random.default_rng(0)
    batches = [strategy.shard_batch({
        "tokens": jnp.asarray(
            rng.integers(0, model.vocab_size, (batch_size, seq)), jnp.int32),
    }) for _ in range(4)]
    compiled = step.lower(state, batches[0]).compile()
    xla_flops = _flops_of(compiled)
    flops_per_step = lm_analytic_flops(model, batch_size, seq)

    for i in range(warmup):
        state, metrics = compiled(state, batches[i % len(batches)])
    float(metrics["loss"])
    t0 = time.perf_counter()
    for i in range(iters):
        state, metrics = compiled(state, batches[i % len(batches)])
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss), f"non-finite LM loss {final_loss}"

    tokens_per_sec = batch_size * (seq - 1) * iters / dt
    row = {
        "model": "lm",
        "size": size,
        "batch_size": batch_size,
        "seq": seq,
        "tokens_per_sec": round(tokens_per_sec, 0),
        "samples_per_sec": round(batch_size * iters / dt, 2),
        "step_time_ms": round(1e3 * dt / iters, 3),
        "flops_per_step": flops_per_step,
        "flops_source": "analytic",
        "achieved_tflops": round(flops_per_step * iters / dt / 1e12, 2),
    }
    peak = peak_flops_per_chip()
    if peak:
        row["mfu"] = round(flops_per_step * iters / dt / peak, 4)
        if xla_flops:
            row["mfu_xla"] = round(xla_flops * iters / dt / peak, 4)
    return row


def bench_host_overhead(steps: int = 192, batch_size: int = 64,
                        unroll: int = 8, log_interval: int = 24) -> dict:
    """Host-overhead microbench: sync-every-step vs async-drain vs unrolled.

    Drives the SAME ``train_epoch`` loop three ways over an identical
    synthetic dataset with a deliberately tiny model (2x64-unit MLP), so
    the device step is far below the host's per-step work and the loop
    overhead — per-step ``float()`` syncs vs boundary drains vs one
    dispatch per ``unroll`` steps — dominates what's measured.  This is the
    async-dispatch-discipline receipt (SCALING.md): the deltas here are
    pure host↔device pipeline stalls, the cost every sub-ms-step TPU
    workload pays when a loop reads a metric on the step it just
    dispatched.
    """
    from dtdl_tpu.data.loader import DataLoader
    from dtdl_tpu.models import MLP
    from dtdl_tpu.parallel.strategy import SingleDevice
    from dtdl_tpu.train import init_state, make_train_step, train_epoch

    strategy = SingleDevice()
    rng = np.random.default_rng(0)
    n = steps * batch_size
    x = rng.normal(size=(n, 64)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int64)
    loader = DataLoader({"image": x, "label": y}, batch_size, shuffle=False)
    tx = optax.sgd(0.01)
    step = make_train_step(strategy)

    def fresh_state():
        return strategy.replicate(init_state(
            MLP(n_units=64), jax.random.PRNGKey(0),
            jnp.zeros((1, 64)), tx))

    modes = {
        "sync": dict(sync_every_step=True),
        "async": dict(),
        f"unroll{unroll}": dict(unroll=unroll),
    }
    row = {"model": "host_overhead", "batch_size": batch_size,
           "steps": steps, "log_interval": log_interval, "unroll": unroll}
    rates = {}
    for name, kw in modes.items():
        state = fresh_state()
        # epoch 0 = warmup (compile); epoch 1 = timed
        state, _ = train_epoch(step, state, loader, strategy,
                               log_interval=log_interval, **kw)
        t0 = time.perf_counter()
        state, means = train_epoch(step, state, loader, strategy,
                                   log_interval=log_interval, **kw)
        dt = time.perf_counter() - t0
        assert np.isfinite(means["loss"])
        rates[name] = steps / dt
        row[f"{name}_steps_per_sec"] = round(steps / dt, 1)
    row["async_speedup_vs_sync"] = round(rates["async"] / rates["sync"], 3)
    row[f"unroll{unroll}_speedup_vs_sync"] = round(
        rates[f"unroll{unroll}"] / rates["sync"], 3)
    return row


def bench_observability(steps: int = 192, batch_size: int = 64,
                        log_interval: int = 24) -> dict:
    """Observability overhead receipt: the SAME async ``train_epoch``
    with the obs layer off vs fully on (tracer + recompile sentinel +
    goodput meter).

    Uses the host-overhead harness's deliberately tiny model so the
    host-side loop dominates — the worst case for per-step span/sentinel
    bookkeeping.  The contract (ISSUE 3): ``overhead_frac`` (1 -
    on/off steps/sec) stays under 2%; anything more means a span or
    sentinel snuck device work or allocation into the hot path.
    """
    from dtdl_tpu.data.loader import DataLoader
    from dtdl_tpu.models import MLP
    from dtdl_tpu.obs import GoodputMeter, Observer
    from dtdl_tpu.parallel.strategy import SingleDevice
    from dtdl_tpu.train import init_state, make_train_step, train_epoch

    strategy = SingleDevice()
    rng = np.random.default_rng(0)
    n = steps * batch_size
    x = rng.normal(size=(n, 64)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int64)
    loader = DataLoader({"image": x, "label": y}, batch_size, shuffle=False)
    tx = optax.sgd(0.01)
    step = make_train_step(strategy)

    def fresh_state():
        return strategy.replicate(init_state(
            MLP(n_units=64), jax.random.PRNGKey(0),
            jnp.zeros((1, 64)), tx))

    def run(observer):
        state = fresh_state()
        # epoch 0 = warmup (compile); epoch 1 = timed
        state, _ = train_epoch(step, state, loader, strategy,
                               log_interval=log_interval,
                               observer=observer)
        if observer is not None:
            # drop the warmup windows: the compile stall would otherwise
            # BE the reported step-time p99
            from dtdl_tpu.obs import LogHistogram
            observer.step_time_s = LogHistogram()
        t0 = time.perf_counter()
        state, means = train_epoch(step, state, loader, strategy,
                                   log_interval=log_interval,
                                   observer=observer)
        dt = time.perf_counter() - t0
        assert np.isfinite(means["loss"])
        return steps / dt

    off = run(None)
    obs = Observer(trace=True, sentinel="warn",
                   goodput=GoodputMeter(samples_per_step=batch_size))
    on = run(obs)
    return {"model": "observability", "batch_size": batch_size,
            "steps": steps, "log_interval": log_interval,
            "off_steps_per_sec": round(off, 1),
            "on_steps_per_sec": round(on, 1),
            "overhead_frac": round(1.0 - on / off, 4),
            "trace_events": len(obs.tracer),
            "recompile_events": len(obs.sentinel.events),
            "step_time_p99_ms": round(
                obs.step_time_s.p99 * 1e3, 3)}


def bench_robustness(steps: int = 48, batch_size: int = 256,
                     log_interval: int = 12) -> dict:
    """Guard-overhead receipt: the SAME async ``train_epoch`` with the
    resil step guard off vs folded into the compiled step (policy=skip,
    host observe at every drain).

    The guard's in-jit cost — one global grad norm + a scalar-predicated
    state select — is DEVICE work that scales with parameter count but
    not batch, so (unlike the host-overhead/observability rows, whose
    additions are host-side constants) a sub-ms toy step would inflate
    the ratio far beyond anything a real workload sees.  The row
    therefore uses a wider MLP at a step time in the low milliseconds —
    the small end of real training steps; on anything larger the
    fraction only shrinks, since the guard cost is ~O(params) against
    O(params x batch) compute.  The contract (ISSUE 5, same bar as the
    observer): ``overhead_frac`` stays under 2%.  ``guard_bad_steps``
    must be 0 — a fault-free run proves the guard never fires
    spuriously.
    """
    from dtdl_tpu.data.loader import DataLoader
    from dtdl_tpu.models import MLP
    from dtdl_tpu.parallel.strategy import SingleDevice
    from dtdl_tpu.resil import StepGuard
    from dtdl_tpu.train import init_state, make_train_step, train_epoch

    strategy = SingleDevice()
    rng = np.random.default_rng(0)
    n = steps * batch_size
    dim = 256
    x = rng.normal(size=(n, dim)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int64)
    loader = DataLoader({"image": x, "label": y}, batch_size, shuffle=False)
    tx = optax.sgd(0.01)

    def fresh_state():
        return strategy.replicate(init_state(
            MLP(n_units=512), jax.random.PRNGKey(0),
            jnp.zeros((1, dim)), tx))

    guard = StepGuard(policy="skip")
    modes = {"off": (make_train_step(strategy), None),
             "on": (make_train_step(strategy, guard=guard), guard)}
    states = {k: fresh_state() for k in modes}
    best = {k: 0.0 for k in modes}

    def one_epoch(name):
        step, g = modes[name]
        t0 = time.perf_counter()
        states[name], means = train_epoch(
            step, states[name], loader, strategy,
            log_interval=log_interval, guard=g)
        dt = time.perf_counter() - t0
        assert np.isfinite(means["loss"])
        return steps / dt

    # warmup epoch each (compile), then interleaved repetitions with
    # best-of-N per mode: a ~1% delta is far below this box's run-to-run
    # drift (whole epochs swing 20%+ under ambient load), and load noise
    # is additive-positive — the best epoch of many alternating reps
    # approaches each mode's true floor instead of attributing ambient
    # drift to whichever mode ran second
    for name in modes:
        one_epoch(name)
    for _ in range(6):
        for name in modes:
            best[name] = max(best[name], one_epoch(name))
    return {"model": "robustness", "batch_size": batch_size,
            "steps": steps, "log_interval": log_interval,
            "off_steps_per_sec": round(best["off"], 1),
            "on_steps_per_sec": round(best["on"], 1),
            "overhead_frac": round(1.0 - best["on"] / best["off"], 4),
            **guard.summary()}


def bench_audit() -> dict:
    """Program-shape receipt (ISSUE 15): the pinned-program audit as a
    bench row, so the trajectory files capture drift the way they
    capture throughput.  Per program: collective counts + bytes (jaxpr
    AND compiled HLO), host transfers/callbacks, and donated bytes —
    plus the named drift list against the checked-in baseline
    (dtdl_tpu/analysis/baselines.json; empty = the program shapes are
    exactly what the last intentional rebase recorded)."""
    from dtdl_tpu.analysis import contracts

    runnable, skipped = contracts.runnable_programs()
    reports = contracts.audit_programs(runnable)
    drift = contracts.compare_to_baseline(reports,
                                          contracts.load_baseline())
    row = {"model": "audit",
           "drift": [f.render() for f in drift],
           "drift_findings": len(drift),
           # geometries this process's device count cannot build (the
           # megatron step needs 8) — audited in the test harness's
           # forced 8-device platform instead of silently erroring here
           "skipped": skipped}
    for name, rep in sorted(reports.items()):
        row[name] = {
            "collectives_hlo": {k: v["count"] for k, v in
                                rep["hlo_collectives"].items()},
            "collective_bytes_hlo": sum(
                v["bytes"] for v in rep["hlo_collectives"].values()),
            "collectives_jaxpr": {k: v["count"] for k, v in
                                  rep["jaxpr_collectives"].items()},
            "host_transfers": rep["host_transfers"],
            "callbacks": rep["callbacks"],
            "donated_bytes": rep["donated_bytes"],
            "donated_args": f"{rep['n_donated_args']}/"
                            f"{rep['n_expected_donated']}",
        }
    return row


def bench_kernels(head_dims=(64, 128), seqs=(4096,), iters: int = 2,
                  warmup: int = 1, vocabs=(32768, 256),
                  samp_batch: int = 8, samp_iters: int = 20) -> dict:
    """Kernel-round microbench (round 13): old vs new hot-path kernels.

    **Attention** — fwd+bwd flash attention at B=1/H=1, bf16, causal,
    per (head_dim, seq): the round-12 configuration (standalone
    ``apply_rope`` + the old hardcoded 1024×1024 blocks) against the
    round-13 one (rope fused into the kernels + autotune-table blocks).
    Throughput is USEFUL FLOPs (the goodput convention: causal at the
    computed half, backward at 2x forward, recompute and rope never
    credited) so old and new divide identical numerators.

    **Sampling** — the serve decode epilogue per vocab size: scale +
    top-k + top-p filter + categorical draw over [B, V] logits, sorted
    (descending argsort + cumsum + inverse argsort — the round-12 path,
    kept as ``filter_logits_sorted``) vs sortless (32-round threshold
    bisection — ``filter_logits``).

    Honesty: on CPU the attention kernels run under the Pallas
    interpreter (``interpret: true`` in the row) — block geometry and
    arithmetic are exactly the TPU program, but relative timings mix in
    interpreter overheads, and the rope-fusion HBM win by construction
    cannot show up where there is no HBM (goodput.lm_rope_hbm_bytes
    carries the bytes arithmetic; LM_ROOFLINE.md the expected v5e
    effect).  Default seqs stay short for the same reason — pass
    ``--kernel-seqs 4096,32768`` on a real chip.
    """
    from dtdl_tpu.obs.goodput import lm_rope_hbm_bytes
    from dtdl_tpu.ops.attention import flash_attention, resolve_blocks
    from dtdl_tpu.ops.rope import apply_rope, rope_frequencies
    from dtdl_tpu.serve.sampling import filter_logits, filter_logits_sorted

    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)

    def timed(fn, *args):
        fn_j = jax.jit(fn)
        for _ in range(warmup):
            out = fn_j(*args)
        float(jax.tree.leaves(out)[0].ravel()[0])
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn_j(*args)
        float(jax.tree.leaves(out)[0].ravel()[0])
        return (time.perf_counter() - t0) / iters

    attn = []
    for d in head_dims:
        cos, sin = rope_frequencies(d, max(seqs))
        for s in seqs:
            q, k, v = (jnp.asarray(rng.normal(size=(1, 1, s, d)),
                                   jnp.bfloat16) for _ in range(3))

            def loss_old(q, k, v):
                qr = apply_rope(q, cos[:s], sin[:s])
                kr = apply_rope(k, cos[:s], sin[:s])
                o = flash_attention(qr, kr, v, causal=True,
                                    block_q=1024, block_k=1024)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            def loss_new(q, k, v):
                o = flash_attention(q, k, v, causal=True,
                                    rope=(cos, sin))
                return jnp.sum(o.astype(jnp.float32) ** 2)

            old_s = timed(jax.grad(loss_old, (0, 1, 2)), q, k, v)
            new_s = timed(jax.grad(loss_new, (0, 1, 2)), q, k, v)
            useful = 3 * 2 * 1 * 1 * float(s) * float(s) * d  # fwd+2x bwd
            attn.append({
                "head_dim": d, "seq": s,
                "blocks": list(resolve_blocks(d, s)),
                "old_ms": round(old_s * 1e3, 2),
                "new_ms": round(new_s * 1e3, 2),
                "old_tflops": round(useful / old_s / 1e12, 4),
                "new_tflops": round(useful / new_s / 1e12, 4),
                "speedup": round(old_s / new_s, 3),
                # the HBM traffic the fusion removes at THIS geometry
                # (one layer, B=1/H=1) — the quantity that, not the CPU
                # ms, is the v5e claim (LM_ROOFLINE.md round 13)
                "rope_bytes_saved": int(lm_rope_hbm_bytes(
                    type("C", (), {"n_layers": 1, "n_heads": 1,
                                   "head_dim": d})(), 1, s)),
            })

    samp = []
    for v_sz in vocabs:
        logits = jnp.asarray(rng.normal(size=(samp_batch, v_sz)) * 3,
                             jnp.float32)
        temp = jnp.full((samp_batch,), 0.8, jnp.float32)
        top_k = jnp.full((samp_batch,), 50, jnp.int32)
        top_p = jnp.full((samp_batch,), 0.9, jnp.float32)
        key = jax.random.PRNGKey(0)

        def draw(filt):
            def fn(lg):
                masked = filt(lg, temp, top_k, top_p)
                return jax.random.categorical(key, masked, axis=-1)
            return fn

        sort_s = timed(draw(filter_logits_sorted), logits)
        less_s = timed(draw(filter_logits), logits)
        samp.append({
            "vocab": v_sz, "batch": samp_batch,
            "sorted_us": round(sort_s * 1e6, 1),
            "sortless_us": round(less_s * 1e6, 1),
            "speedup": round(sort_s / less_s, 3),
        })

    return {"model": "kernels", "interpret": interpret,
            "iters": iters, "attention": attn, "sampling": samp}


def bench_serving(size: str = None, slot_sweep=(1, 4, 8),
                  new_tokens: int = 32) -> dict:
    """Serving throughput: prefill vs decode tokens/sec vs batch size.

    Drives the dtdl_tpu.serve engine directly (no scheduler policy in the
    timed region): for each slot count B, prefill B prompts of one bucket
    and run ``new_tokens`` batched decode steps.  The two phases are timed
    separately because they sit on opposite ends of the roofline — prefill
    is one matmul-heavy pass over the whole prompt (compute-bound), decode
    re-reads every weight once per token (HBM-bandwidth-bound), which is
    why decode tokens/sec should scale near-linearly with B until the KV
    reads catch up with the weight reads (SCALING.md "Serving latency
    model").  Value fetch ends each timed region, per the module contract.
    """
    import flax.linen as nn
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.serve import InferenceEngine

    if size is None:
        size = "tiny" if jax.devices()[0].platform == "cpu" else "base"
    model = transformer_lm(size, attn_impl="dense", dtype=jnp.float32)
    prompt_len = min(model.max_seq // 2, 512)
    new_tokens = min(new_tokens, model.max_seq - prompt_len)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    row = {"model": "serving", "size": size, "prompt_len": prompt_len,
           "new_tokens": new_tokens, "sweep": []}
    for B in slot_sweep:
        engine = InferenceEngine(model, params, n_slots=B,
                                 buckets=(prompt_len,))
        greedy = (jnp.zeros(B), jnp.zeros(B, jnp.int32), jnp.ones(B))
        key = jax.random.PRNGKey(0)
        prompts = [rng.integers(0, model.vocab_size, prompt_len)
                   for _ in range(B)]

        def fill(arena, last):
            for slot, p in enumerate(prompts):
                arena, last, _ = engine.prefill(arena, last, slot, p)
            return arena, last

        # warmup: compile prefill + decode once
        arena, last = fill(engine.init_arena(), engine.init_last_tokens())
        arena, last, _ = engine.decode(arena, last, np.ones(B, bool),
                                       key, *greedy)
        # timed prefill (fresh arena, same compiled program)
        arena, last = engine.init_arena(), engine.init_last_tokens()
        t0 = time.perf_counter()
        arena, last = fill(arena, last)
        np.asarray(last)
        dt_prefill = time.perf_counter() - t0
        # timed decode at full occupancy
        active = np.ones(B, bool)
        t0 = time.perf_counter()
        for _ in range(new_tokens):
            arena, last, _ = engine.decode(arena, last, active, key,
                                           *greedy)
        np.asarray(last)
        dt_decode = time.perf_counter() - t0
        row["sweep"].append({
            "batch_size": B,
            "prefill_tokens_per_sec": round(B * prompt_len / dt_prefill, 1),
            "decode_tokens_per_sec": round(B * new_tokens / dt_decode, 1),
            "decode_ms_per_token": round(
                1e3 * dt_decode / new_tokens, 3),
        })
    row["spec"] = bench_spec_decode(model, params)
    row["paged"] = bench_paged()
    row["quant"] = bench_quant(model, params)
    return row


class _ReplayDraft:
    """Perfect drafts replayed from a probe run's recorded sequences —
    the synthetic HIGH-ACCEPTANCE workload.  Greedy decode is
    deterministic, so replaying the probe's continuation drafts exactly
    what the model will say: acceptance ~1 and the sweep measures the
    verify path's mechanism ceiling (one param sweep -> k+1 tokens), the
    way the host-overhead row measures dispatch headroom.  A real
    workload lands between this and the k=0 baseline in proportion to
    its draft source's acceptance rate (SCALING.md "Speculative decoding
    arithmetic")."""

    def __init__(self, seqs):
        self.seqs = [list(s) for s in seqs]

    def propose(self, ctx, k):
        ctx = list(np.asarray(ctx, np.int32))
        for full in self.seqs:
            if ctx == full[:len(ctx)]:
                return np.asarray(full[len(ctx):len(ctx) + k], np.int32)
        return np.zeros((0,), np.int32)


def bench_spec_decode(model, params, n_slots: int = 4,
                      new_tokens: int = 96, ks=(0, 2, 4)) -> list:
    """Speculative-decoding sweep: scheduler-driven tokens/sec at draft
    widths k ∈ {0, 2, 4}, greedy and temperature sampling.

    Greedy rows draft from :class:`_ReplayDraft` (probe-run replay, the
    high-acceptance synthetic workload — see its docstring); temperature
    rows draft with the production n-gram source against near-uniform
    sampled content, the low-acceptance end (rejection sampling accepts
    a draft with probability p(draft), small at high entropy — the
    acceptance_rate field is the calibration).  k=0 is the plain
    continuous-batching baseline through the SAME scheduler, so the
    comparison isolates verify-vs-decode.  Each config runs once
    unmeasured to compile its programs, then re-runs timed.
    """
    from dtdl_tpu.serve import InferenceEngine, NGramDraft, Request, \
        SampleParams, Scheduler

    engine = InferenceEngine(model, params, n_slots=n_slots)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, n).tolist()
               for n in rng.integers(8, 16, 2 * n_slots)]
    # probe: record each prompt's greedy continuation once (plain decode,
    # also the warmup for the prefill/decode programs)
    probes = [Request(p, new_tokens) for p in prompts]
    Scheduler(engine, harvest_lag=1).run(probes)
    replay = _ReplayDraft([list(r.prompt) + r.tokens for r in probes])
    out = []
    for k in ks:
        for temp in (0.0, 0.8):
            sp = SampleParams(temperature=temp,
                              top_p=0.95 if temp else 1.0)
            draft = replay if temp == 0.0 else NGramDraft()

            def run():
                reqs = [Request(p, new_tokens, sampling=sp, speculate=k)
                        for p in prompts]
                sched = Scheduler(engine, harvest_lag=1, draft=draft)
                sched.run(reqs)
                return sched.metrics.summary()

            run()                      # warmup: compile + caches
            s = run()                  # timed (wall between first admit
            out.append({               # and last harvest, per ServeMetrics)
                "k": k, "temperature": temp,
                "decode_tokens_per_sec": s["decode_tokens_per_sec"],
                "tokens_per_step": s["tokens_per_step_mean"],
                "acceptance_rate": s["spec_acceptance_rate"],
                "draft_s": s["draft_s"],
            })
    return out


def bench_paged(size: str = "small", n_slots: int = 4,
                page_size: int = 64, new_tokens: int = 8) -> list:
    """Paged-KV sweep: dense vs paged vs paged+prefix-cache on
    repeated-system-prompt traffic (ISSUE 6 acceptance).

    The traffic is the production shape the prefix cache exists for:
    every request shares a multi-page system prompt (3/4 of the
    context) and differs only in a short unique suffix.  Dense and
    prefix-off paged rows prefill the FULL prompt per request (through
    its big bucket); the prefix-cache row computes the shared pages
    once per run and maps them read-only into every later admission,
    so those admissions re-enter through the small SUFFIX bucket — the
    ttft_s_mean gap between the dense and prefix rows is the measured
    cache win, and prefix_hit_rate / prefill_tokens_saved are the
    receipts that the skip actually happened (the cache is
    per-Scheduler, so each timed run pays its own one cold prefill —
    no cross-run warm state flatters the row).  The traffic is ONE
    admission wave (n_requests == n_slots) so ttft_s_mean measures
    prefill, not queue wait behind decode, and the sweep uses the
    'small' model even on CPU — at 'tiny' scale the skipped prefill
    FLOPs drown in per-dispatch host overhead and the row measures
    nothing.  Decode throughput is its own field; on TPU it touches
    the same HBM bytes either way (pages are layout, not compute; on
    this CPU box the table gather shows up as a decode tax the
    roofline hides).  The paged win proper is capacity — slots per
    HBM byte — priced analytically in SCALING.md "Paged KV
    arithmetic".  The two paged rows share ONE engine (the prefix
    cache is scheduler policy), so the whole sweep compiles two
    program sets: dense and paged.
    """
    import flax.linen as nn
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.serve import InferenceEngine, Request, Scheduler

    model = transformer_lm(size, attn_impl="dense", dtype=jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    n_sys = (3 * model.max_seq // 4) // page_size * page_size
    system = rng.integers(0, model.vocab_size, n_sys).tolist()
    new_tokens = min(new_tokens,
                     model.max_seq - n_sys - page_size)
    prompts = [system + rng.integers(0, model.vocab_size,
                                     int(n)).tolist()
               for n in rng.integers(page_size // 2, page_size,
                                     n_slots)]
    dense = InferenceEngine(model, params, n_slots=n_slots)
    paged = InferenceEngine(model, params, n_slots=n_slots,
                            page_size=page_size)
    out = []
    for label, engine, prefix in (("dense", dense, False),
                                  ("paged", paged, False),
                                  ("paged+prefix", paged, True)):

        def run():
            reqs = [Request(p, new_tokens) for p in prompts]
            sched = Scheduler(engine, harvest_lag=1,
                              prefix_cache=prefix)
            sched.run(reqs)
            return sched.metrics.summary()

        run()                      # warmup: compile full + suffix buckets
        s = run()                  # timed
        out.append({
            "arena": label,
            "page_size": page_size if engine.paged else 0,
            "decode_tokens_per_sec": s["decode_tokens_per_sec"],
            "ttft_s_mean": s["ttft_s_mean"],
            "prefix_hit_rate": s["prefix_hit_rate"],
            "prefill_tokens_saved": s["prefill_tokens_saved"],
            "pages_in_use_peak": s["pages_in_use_peak"],
        })
    return out


def bench_kv_hierarchy(size: str = "small", page_size: int = 64,
                       new_tokens: int = 8) -> dict:
    """Hierarchical KV cache row (round 23 acceptance).

    One shared-system-prompt request measured at every tier of the
    hierarchy: **cold** (full prefill, the price the cache avoids),
    **HBM hit** (the round-6 prefix cache: suffix-only prefill),
    **host hit** (the pages were evicted to the host-DRAM spill store
    and re-enter via the batched inject path), **disk hit** (host
    budget of one byte forces every spill through the checksummed
    mmap file).  The claim the row must carry: restore beats
    recompute — ``ttft_s_host_hit < ttft_s_cold`` at 'small' scale,
    because injecting ~0.5 MB/page over PCIe/DRAM is cheaper than
    recomputing ~0.8k tokens of prefill FLOPs (break-even priced in
    SCALING.md "Memory hierarchy arithmetic").  Eviction is forced
    the honest way — a bounded page pool plus distinct-content churn
    traffic — not by poking allocator internals, so the row exercises
    the same spill-on-evict path production would.

    The fleet half is a correctness drill, not a throughput number:
    a two-replica Router with the prefix directory on, one replica
    killed mid-traffic — requests_lost must be 0 and every token
    identical to a ``prefix_directory=False`` oracle fleet (the
    directory may only change WHERE work runs, never what it emits).
    """
    import tempfile

    import flax.linen as nn
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.resil import FaultPlan
    from dtdl_tpu.resil.faults import replica_site
    from dtdl_tpu.serve import InferenceEngine, Request, Router, Scheduler

    model = transformer_lm(size, attn_impl="dense", dtype=jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    n_sys = (3 * model.max_seq // 4) // page_size * page_size
    n_sys_pages = n_sys // page_size
    system = rng.integers(0, model.vocab_size, n_sys).tolist()
    suffix = lambda: rng.integers(0, model.vocab_size,
                                  page_size // 2).tolist()
    churn_prompt = lambda: rng.integers(0, model.vocab_size,
                                        n_sys + page_size // 2).tolist()
    # pool = cached system pages + one in-flight churn request, minus a
    # deficit that forces the allocator to evict (and thus spill) —
    # two churn waves push the WHOLE system chain out of HBM
    per_req = n_sys_pages + 2
    engine = InferenceEngine(model, params, n_slots=2,
                             page_size=page_size,
                             n_pages=n_sys_pages + per_req + 2)
    host_budget = 64 << 20

    def ttft(sched, prompt):
        r = Request(prompt, new_tokens)
        sched.run([r])
        assert r.error is None, r.error
        return round(r.t_first - r.t_submit, 6)

    def churn(sched, waves=2):
        for _ in range(waves):
            sched.run([Request(churn_prompt(), new_tokens)])

    def phases(**spill_kw):
        s = Scheduler(engine, harvest_lag=1, **spill_kw)
        cold = ttft(s, system + suffix())
        hbm = ttft(s, system + suffix())
        churn(s)
        hot = ttft(s, system + suffix())
        return cold, hbm, hot, s.metrics.summary()

    # warmup: one full cycle compiles every bucket + the extract/inject
    # variants, so the timed phases below measure work, not compiles
    phases(spill_host_bytes=host_budget)

    cold, hbm, host_hit, m = phases(spill_host_bytes=host_budget)
    with tempfile.TemporaryDirectory() as tmp:
        _, _, disk_hit, md = phases(spill_host_bytes=1, spill_dir=tmp,
                                    spill_disk_bytes=1 << 30)

    row = {
        "model": "kv_hierarchy", "size": size, "page_size": page_size,
        "system_tokens": n_sys, "new_tokens": new_tokens,
        "ttft_s_cold": cold,
        "ttft_s_hbm_hit": hbm,
        "ttft_s_host_hit": host_hit,
        "ttft_s_disk_hit": disk_hit,
        "restore_beats_recompute": host_hit < cold,
        "kv_spill_pages_spilled": m["pages_spilled"],
        "kv_spill_pages_restored": m["pages_restored"],
        "kv_spill_bytes": m["spill_bytes"],
        "kv_spill_restore_s": m["restore_s"],
        "kv_spill_host_hits": m["spill_host_hits"],
        "kv_spill_disk_hits": md["spill_disk_hits"],
    }

    # --- fleet prefix-directory drill (tiny model: correctness only) --
    tiny = transformer_lm("tiny", vocab_size=64, d_model=32, n_layers=2,
                          n_heads=2, d_ff=64, max_seq=48,
                          attn_impl="dense", dtype=jnp.float32)
    tparams = nn.unbox(tiny.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    teng = InferenceEngine(tiny, tparams, n_slots=2, buckets=(8, 16),
                           page_size=8)
    sys9 = list(range(1, 10))
    reqs = lambda: [Request(sys9 + [20 + i, 21 + i], 4)
                    for i in range(6)]
    fkw = dict(sched_kwargs={"harvest_lag": 1}, retry_budget=3,
               probe_interval_s=0.01, watchdog_s=0.15)
    with Router(teng, n_replicas=2, prefix_directory=False,
                **fkw) as off:
        off.run(reqs())
        want = [r.tokens for r in off.run(reqs())]
    plan = FaultPlan().at(replica_site(0, "loop"), 0)
    with Router(teng, n_replicas=2, plan=plan, auto_restart=True,
                **fkw) as router:
        router.run(reqs())                 # replica 0 dies mid-wave
        time.sleep(0.05)
        wave2 = router.run(reqs())
        fs = router.summary()
    row.update({
        "prefix_directory_hits": fs["fleet_directory_hits"],
        "prefix_directory_tokens_saved":
            fs["fleet_directory_tokens_saved"],
        "prefix_directory_invalidations":
            fs["fleet_directory_invalidations"],
        "prefix_directory_requests_lost":
            0 if fs["fleet_accounting_ok"]
            and fs["fleet_requests_failed"] == 0
            and fs["fleet_requests_expired"] == 0
            else fs["fleet_requests_failed"] + fs["fleet_requests_expired"],
        "prefix_directory_token_divergence": sum(
            1 for r, w in zip(wave2, want) if r.tokens != w),
        "prefix_directory_evictions": fs["fleet_evictions"],
    })
    return row


def bench_chunked_prefill(size: str = "small", n_slots: int = 4,
                          chunk_tokens: int = 4,
                          new_tokens: int = 32) -> dict:
    """Chunked-prefill interference row (ISSUE 14 acceptance).

    The workload is the interference shape Sarathi-Serve targets:
    short requests decode steadily while LONG prompts arrive mid-run.
    With whole-prompt prefill, each long admission stalls every
    in-flight decode by a full prefill latency — the decoders' p99
    inter-token gap IS the prefill time.  With ``chunk_tokens`` the
    prompt rides per-step verify chunks sharing the decoders' compiled
    step, so the tail collapses to ~one chunk of extra compute per
    step.  Driven at ``harvest_lag=0`` so each step delivers exactly
    one token per decoding slot and the per-step wall time is the
    honest inter-token latency sample; p50/p99 are over those steps.
    Greedy token identity between the two runs is asserted into the
    row (``token_identical``) — chunking must change WHEN tokens
    appear, never WHICH.  ``decode_steps_delayed_by_prefill`` /
    ``prefill_chunks`` are the mechanism receipts.

    The default ``chunk_tokens=4`` is this COMPUTE-BOUND box's knee
    (measured ~1.7x p99 improvement; 8 gives ~1.25x, 32+ inverts): on
    CPU a chunk step pays the verify window as real compute, so small
    chunks win.  On TPU the verify sweep rides the bandwidth-bound
    parameter read (the spec-decode argument) and the trade curve
    moves toward Sarathi-sized budgets (hundreds of tokens) — the
    SCALING.md round-19 arithmetic.

    The row also carries a ``disagg`` receipt at 'tiny' scale: a
    prefill+decode role fleet (page-granular KV handoff through the
    Router) vs the single mixed scheduler — token-identical, with the
    migration/handoff counters.  One box cannot show the real
    disaggregation win (prefill and decode contend for the same CPU);
    the isolation claim is priced in SCALING.md round 19.
    """
    import flax.linen as nn
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.serve import (InferenceEngine, Request, Router,
                                Scheduler)

    model = transformer_lm(size, attn_impl="dense", dtype=jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    engine = InferenceEngine(model, params, n_slots=n_slots)
    rng = np.random.default_rng(0)
    long_len = 3 * model.max_seq // 4
    steady_prompts = [rng.integers(0, model.vocab_size, 24).tolist()
                      for _ in range(2)]
    long_prompts = [rng.integers(0, model.vocab_size, long_len).tolist()
                    for _ in range(2)]

    def run(chunk):
        sched = Scheduler(engine, harvest_lag=0, chunk_tokens=chunk)
        steady = [Request(list(p), 3 * new_tokens)
                  for p in steady_prompts]
        for r in steady:
            sched.submit(r)
        gaps = []
        for i in range(6 * new_tokens):
            if i == 4:                 # long prompts land mid-decode
                for p in long_prompts:
                    sched.submit(Request(list(p), 4))
            t0 = time.perf_counter()
            sched.step()
            gaps.append(time.perf_counter() - t0)
            if all(r.done for r in steady):
                break
        sched.shutdown(drain=True)
        arr = np.sort(np.asarray(gaps))
        pick = lambda q: float(arr[int(q * (len(arr) - 1))])  # noqa: E731
        return (pick(0.5), pick(0.99), sched.metrics.summary(),
                [r.tokens for r in steady])

    run(None)                          # warmup: compile both flavors
    run(chunk_tokens)
    p50_w, p99_w, m_w, toks_w = run(None)
    p50_c, p99_c, m_c, toks_c = run(chunk_tokens)

    # disaggregation receipt at 'tiny' scale: identity + handoff books
    tmodel = transformer_lm("tiny", attn_impl="dense",
                            dtype=jnp.float32)
    tparams = nn.unbox(tmodel.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"])
    peng = InferenceEngine(tmodel, tparams, n_slots=2, page_size=16)
    dprompts = [rng.integers(0, tmodel.vocab_size,
                             int(n)).tolist()
                for n in rng.integers(5, 20, 6)]
    refs = [Request(list(p), 8) for p in dprompts]
    Scheduler(peng, harvest_lag=1).run(refs)
    with Router(peng, roles=["prefill", "decode"],
                sched_kwargs={"harvest_lag": 1, "chunk_tokens": 16},
                probe_interval_s=0.01, watchdog_s=1.0) as router:
        reqs = router.run([Request(list(p), 8) for p in dprompts])
        fs = router.summary()
    disagg_identical = all(
        r.error is None and r.tokens == ref.tokens
        for r, ref in zip(reqs, refs))
    handoff_s = sum(rep["kv_handoff_s"] for rep in fs["replicas"])

    return {
        "model": "chunked_prefill", "size": size,
        "chunk_tokens": chunk_tokens,
        "token_identical": toks_w == toks_c,
        "whole": {
            "p50_tok_latency_s": round(p50_w, 6),
            "p99_tok_latency_s": round(p99_w, 6),
            "decode_steps_delayed_by_prefill":
                m_w["decode_steps_delayed_by_prefill"],
        },
        "chunked": {
            "p50_tok_latency_s": round(p50_c, 6),
            "p99_tok_latency_s": round(p99_c, 6),
            "prefill_chunks": m_c["prefill_chunks"],
            "chunk_tokens_total": m_c["chunk_tokens"],
            "decode_steps_delayed_by_prefill":
                m_c["decode_steps_delayed_by_prefill"],
        },
        "p99_improvement_x": round(p99_w / p99_c, 3) if p99_c else None,
        "disagg": {
            "token_identical": disagg_identical,
            "migrations": fs["fleet_migrations"],
            "kv_handoff_pages": fs["fleet_kv_handoff_pages"],
            "kv_handoff_s_mean": round(
                handoff_s / max(1, fs["fleet_migrations"]), 6),
            "accounting_ok": fs["fleet_accounting_ok"],
        },
    }


def bench_multitenant(n_slots: int = 4, new_tokens: int = 32,
                      n_adapters: int = 4, rank: int = 8) -> dict:
    """Multi-tenant serving row (round 22): the cost of tenancy.

    Three questions, each against its own control through the SAME
    scheduler on one LoRA-capable engine (adapter ids / grammar masks
    are data, so every config below reuses ONE compiled program set):

    * **multi-LoRA** — delivered tokens/sec with every request on the
      base model, all on ONE adapter, and round-robined across N
      adapters.  The N-adapter rate over the 1-adapter rate is the
      batching claim: tenancy costs a bank gather, not a batch split
      (a per-tenant engine would divide throughput by N).
    * **grammar** — unconstrained vs JSON-schema-constrained decode.
      The constrained run pays a host-side DFA advance per harvested
      token and a [B, V] mask upload per step, both off the device's
      critical path; the ratio prices them.
    * **streaming** — mean time-to-first-STREAMED-token beside the
      engine TTFT: the stream delivers at the first lag-harvest
      boundary, so the gap is ~harvest_lag steps, not a new sync.
    """
    import os
    import tempfile

    import flax.linen as nn
    from dtdl_tpu.ckpt import save_weights
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.serve import (InferenceEngine, Request, Scheduler,
                                TokenStream, adapter_template, byte_vocab,
                                compile_json_schema)

    model = transformer_lm("tiny", attn_impl="dense", dtype=jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    tmp = tempfile.mkdtemp(prefix="bench_lora_")
    tpl = adapter_template(params, rank=rank)
    paths = []
    for i in range(n_adapters):
        tree = jax.tree_util.tree_map(
            lambda x: np.asarray(rng.normal(0, 0.02, x.shape),
                                 np.float32), tpl)
        p = os.path.join(tmp, f"tenant_{i}")
        save_weights(p, tree)
        paths.append(p)
    engine = InferenceEngine(model, params, n_slots=n_slots,
                             lora_rank=rank,
                             lora_adapters=n_adapters + 1)
    prompts = [rng.integers(0, model.vocab_size, int(n)).tolist()
               for n in rng.integers(8, 16, 2 * n_slots)]
    eos = model.vocab_size - 1
    dfa = compile_json_schema(
        {"type": "object",
         "properties": {"a": {"type": "integer"},
                        "b": {"type": "string"}},
         "required": ["a", "b"]},
        byte_vocab(model.vocab_size), eos_id=eos)

    def run(tenants=(None,), grammar=None, stream=False):
        first_cb = {}

        def mk_stream(i):
            if not stream:
                return None
            return TokenStream(callback=lambda new, i=i: first_cb
                               .setdefault(i, time.perf_counter()))

        reqs = [Request(list(p), new_tokens,
                        adapter=tenants[i % len(tenants)],
                        grammar=grammar,
                        eos_id=(eos if grammar is not None else None),
                        stream=mk_stream(i))
                for i, p in enumerate(prompts)]
        t0 = {r.rid: time.perf_counter() for r in reqs}
        sched = Scheduler(engine, harvest_lag=2)
        sched.run(reqs)
        s = sched.metrics.summary()
        if stream:
            gaps = [first_cb[i] - t0[r.rid]
                    for i, r in enumerate(reqs) if i in first_cb]
            s["ttfst_s_mean"] = round(float(np.mean(gaps)), 6) \
                if gaps else None
        return s

    run()                                       # warmup: compile + bank
    base = run()
    one = run(tenants=(paths[0],))
    many = run(tenants=[None] + paths)
    con = run(grammar=dfa)
    strm = run(stream=True)
    tps = "decode_tokens_per_sec"
    return {
        "model": "multitenant", "n_slots": n_slots,
        "n_adapters": n_adapters, "rank": rank,
        "lora": {
            "base_tokens_per_sec": base[tps],
            "one_adapter_tokens_per_sec": one[tps],
            "n_adapters_tokens_per_sec": many[tps],
            "bank_loads": engine.adapter_bank.n_loads,
            "tokens_by_adapter": many["tokens_by_adapter"],
        },
        "grammar": {
            "free_tokens_per_sec": base[tps],
            "constrained_tokens_per_sec": con[tps],
            "grammar_rejected_tokens": con["grammar_rejected_tokens"],
            "dfa_states": dfa.n_states,
            "dfa_bytes": dfa.nbytes(),
        },
        "stream": {
            "ttft_s_mean": strm["ttft_s_mean"],
            "ttfst_s_mean": strm["ttfst_s_mean"],
            "stream_deliveries": strm["stream_deliveries"],
        },
        "compiled_decode_programs": engine.compile_stats()["decode"],
    }


def bench_quant(model, params, n_slots: int = 4, page_size: int = 32,
                new_tokens: int = 48) -> list:
    """Quantized-serving sweep: f32 / w8 / w8+kv8 / w8f+kvf8 ×
    dense/paged (ISSUE 7 acceptance; fp8 rows kernel round 2).

    Eight engines over the same tiny model and traffic, scheduler-driven
    like the spec/paged rows (warmup run compiles, second run is timed).
    Decode is HBM-bandwidth-bound, so on TPU tokens/sec tracks the
    ``bytes_per_token`` receipt each row carries from
    ``compile_stats()['quant']`` — ``(param_bytes + kv_arena_bytes) /
    n_slots``, the roofline numerator.  On this CPU box the timing is
    honest but NOT the roofline: XLA:CPU pays the int8→f32 convert as
    real compute instead of hiding it under an HBM read, so the w8 rows
    can be slower than f32 here while the byte receipts — the thing
    that transfers to TPU — shrink ~4x (f32 weights) and >2x (KV arena;
    SCALING.md "Quantized serving arithmetic").  The paged rows all get
    the SAME ``kv_pool_bytes`` budget (the f32 dense-equivalent pool),
    so the int8 row's ``n_pages`` IS the capacity-multiplier receipt:
    slots-per-HBM-byte, measured in pages, at fixed bytes.  The fp8
    rows (``quantize_weights='w8f'`` / ``kv_dtype='fp8'``) keep the
    one-byte payloads and shrink the *sidecars* — bf16 scales vs int8's
    f32 — so the DENSE fp8 row's bytes_per_token must land strictly
    below the dense w8kv8 row, and the PAGED fp8 row (whose arena
    always fills the fixed budget) must hold strictly more pages than
    the int8 one (the kernel-round-2 acceptance receipts).
    """
    from dtdl_tpu.serve import InferenceEngine, Request, Scheduler

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, int(n)).tolist()
               for n in rng.integers(8, 16, n_slots)]
    new_tokens = min(new_tokens, model.max_seq - 16)
    # one fixed HBM budget for every paged row: what the f32 pool needs
    # at dense-equivalent capacity
    probe = InferenceEngine(model, params, n_slots=n_slots,
                            page_size=page_size)
    pool_budget = probe.page_bytes * probe.n_pages
    out = []
    for arena in ("dense", "paged"):
        for label, w8, kv in (("f32", False, None),
                              ("w8", True, None),
                              ("w8kv8", True, "int8"),
                              ("w8fkvf8", "w8f", "fp8")):
            kw = (dict(page_size=page_size, kv_pool_bytes=pool_budget)
                  if arena == "paged" else {})
            engine = InferenceEngine(model, params, n_slots=n_slots,
                                     quantize_weights=w8, kv_dtype=kv,
                                     **kw)

            def run():
                reqs = [Request(p, new_tokens) for p in prompts]
                sched = Scheduler(engine, harvest_lag=1)
                sched.run(reqs)
                return sched.metrics.summary()

            run()                  # warmup: compile prefill + decode
            s = run()              # timed
            q = engine.compile_stats()["quant"]
            out.append({
                "arena": arena, "weights": label,
                "kv_dtype": q["kv_dtype"] or "f32",
                "decode_tokens_per_sec": s["decode_tokens_per_sec"],
                "ttft_s_mean": s["ttft_s_mean"],
                "param_bytes": q["param_bytes"],
                "kv_arena_bytes": q["kv_arena_bytes"],
                "bytes_per_token": q["decode_hbm_bytes_per_token"],
                "n_pages": engine.n_pages,
            })
    return out


def bench_paged_kernel(page_size: int = 8, n_ptab: int = 8, batch: int = 4,
                       heads: int = 4, head_dim: int = 64,
                       widths=(1, 5), iters: int = 3) -> dict:
    """Isolated paged-attend microbench: dense vs gather-paged vs the
    Pallas paged kernel, at decode (S=1) and verify (S=k+1) widths
    (kernel round 2 acceptance).

    Three jitted attends over the SAME pooled arena geometry
    ``[n_pages, H, page, D]`` and per-slot page tables, quant off and
    int8 (fused scales):

    * **dense** — attend over a contiguously materialized
      [B, H, S_ctx, D] K/V (the no-paging floor: same FLOPs, no
      indirection).
    * **gather** — ``jnp.take`` the slot's whole page-table worth of
      pages out of the pool, then attend (what the engine's gather path
      does per step: the pool crosses HBM into a scratch copy and again
      into the attend).
    * **kernel** — ``dtdl_tpu.ops.paged_attention``: the grid walks the
      page table *inside* the kernel, DMA-ing only live pages pool→VMEM
      once, scales folded into tile loads.

    The TPU claim is the **bytes column**, not this box's ms: per step
    the gather path moves ``2·B·n_ptab·page·H·D`` payload bytes twice
    (pool→scratch, scratch→compute) while the kernel moves
    ``2·B·ceil((pos+1)/page)·page·H·D`` once — ``bytes_x`` is that
    ratio at the benchmarked occupancy, >1 whenever slots are not at
    max context (and ≥2 even there).  Honesty: on CPU the kernel runs
    under the Pallas interpreter (``interpret: true``), so its ms here
    is interpreter overhead, not a TPU prediction — the v5e re-sweep is
    the verification (LM_ROOFLINE.md §9).
    """
    from dtdl_tpu.ops.paged_attention import paged_attention

    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)
    n_pages = batch * n_ptab + 1
    s_ctx = n_ptab * page_size
    d = head_dim
    pk, pv = (jnp.asarray(rng.normal(size=(n_pages, heads, page_size, d)),
                          jnp.float32) for _ in range(2))
    table = jnp.asarray(
        1 + np.arange(batch * n_ptab).reshape(batch, n_ptab), jnp.int32)
    # mid-range occupancy: slots at ~3/4 context (the shape serving
    # actually runs at — full-context slots are the retirement edge)
    base_pos = 3 * s_ctx // 4 - 1
    active = jnp.ones((batch,), jnp.int32)
    scale = 1.0 / math.sqrt(d)

    def timed(fn, *args):
        fn_j = jax.jit(fn)
        jax.block_until_ready(fn_j(*args))        # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn_j(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    def gather_attend(q, pos):
        k = jnp.take(pk, table, axis=0)           # [B, n_ptab, H, page, D]
        v = jnp.take(pv, table, axis=0)
        k = k.transpose(0, 2, 1, 3, 4).reshape(batch, heads, s_ctx, d)
        v = v.transpose(0, 2, 1, 3, 4).reshape(batch, heads, s_ctx, d)
        return _masked_attend(q, k, v, pos)

    def _masked_attend(q, k, v, pos):
        s_new = q.shape[2]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
        cols = jnp.arange(s_ctx)[None, None, None, :]
        qpos = (pos[:, None, None, None]
                + jnp.arange(s_new)[None, None, :, None])
        s = jnp.where(cols <= qpos, s * scale, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)

    k_dense = jnp.take(pk, table, axis=0).transpose(0, 2, 1, 3, 4) \
        .reshape(batch, heads, s_ctx, d)
    v_dense = jnp.take(pv, table, axis=0).transpose(0, 2, 1, 3, 4) \
        .reshape(batch, heads, s_ctx, d)

    it = 4                                        # f32 payload bytes
    rows = []
    for s_new in widths:
        pos = jnp.full((batch,), base_pos - (s_new - 1), jnp.int32)
        q = jnp.asarray(rng.normal(size=(batch, heads, s_new, d)),
                        jnp.float32)
        dense_s = timed(lambda q, pos: _masked_attend(q, k_dense, v_dense,
                                                      pos), q, pos)
        gather_s = timed(gather_attend, q, pos)
        kernel_s = timed(
            lambda q, pos: paged_attention(q, pk, pv, table, pos, active,
                                           scale=scale), q, pos)
        live_pages = int(np.ceil((base_pos + 1) / page_size))
        gather_bytes = 2 * 2 * batch * n_ptab * page_size * heads * d * it
        kernel_bytes = 2 * batch * live_pages * page_size * heads * d * it
        rows.append({
            "s_new": s_new, "phase": "decode" if s_new == 1 else "verify",
            "dense_ms": round(dense_s * 1e3, 3),
            "gather_ms": round(gather_s * 1e3, 3),
            "kernel_ms": round(kernel_s * 1e3, 3),
            "gather_hbm_bytes": gather_bytes,
            "kernel_hbm_bytes": kernel_bytes,
            "bytes_x": round(gather_bytes / kernel_bytes, 3),
        })
    return {"model": "paged_kernel", "interpret": interpret,
            "page_size": page_size, "n_ptab": n_ptab, "batch": batch,
            "heads": heads, "head_dim": head_dim, "iters": iters,
            "occupancy": round((base_pos + 1) / s_ctx, 3), "rows": rows}


def bench_fleet(n_requests: int = 24, new_tokens: int = 24) -> dict:
    """Fleet row (ISSUE 9): Router throughput at 1 vs 2 replicas, plus
    a kill-one-replica failover drill.

    Throughput: the same synthetic traffic driven through the Router's
    least-loaded dispatch over thread-hosted replicas SHARING one
    engine (XLA executions release the GIL, so two replicas can overlap
    device work; at tiny scale host dispatch dominates, so treat the
    ratio as a lower bound — on real HBM-bound decode each replica is
    its own device and the scaling is near-linear by construction).

    Failover: a loop-site fault kills replica 0's worker mid-traffic.
    Receipts: ``time_to_evict_s`` (worker death → the EVICTED health
    transition, i.e. detection latency through the watchdog/probe
    path), ``requests_retried``, and ``requests_lost`` — which must be
    ZERO: every accepted request reaches a terminal state, retried ones
    token-identical by greedy determinism (the fleet invariant,
    tests/test_fleet.py)."""
    import flax.linen as nn
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.resil import FaultPlan
    from dtdl_tpu.resil.faults import replica_site
    from dtdl_tpu.serve import InferenceEngine, Request, Router, Scheduler

    model = transformer_lm("tiny", attn_impl="dense", dtype=jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    engine = InferenceEngine(model, params, n_slots=4, buckets=(64,))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size,
                            int(rng.integers(8, 64))).tolist()
               for _ in range(n_requests)]

    def traffic():
        return [Request(list(p), new_tokens) for p in prompts]

    # warm the compiled programs outside every timed region
    Scheduler(engine, harvest_lag=4).run(
        [Request(list(prompts[0]), 4)])

    row = {"model": "fleet", "n_requests": n_requests,
           "new_tokens": new_tokens, "replicas": []}
    for n_rep in (1, 2):
        with Router(engine, n_replicas=n_rep,
                    sched_kwargs={"harvest_lag": 4}) as router:
            t0 = time.perf_counter()
            router.run(traffic(), timeout_s=600)
            wall = time.perf_counter() - t0
            s = router.summary()
        row["replicas"].append({
            "n_replicas": n_rep,
            "wall_s": round(wall, 4),
            "decode_tokens_per_sec": round(
                s["fleet_decode_tokens"] / wall, 1) if wall else 0.0,
            "ttft_s_p50": s.get("fleet_ttft_s_p50", 0.0),
            "ttft_s_p99": s.get("fleet_ttft_s_p99", 0.0),
        })

    # the failover drill: kill replica 0's worker on its 4th iteration
    plan = FaultPlan().at(replica_site(0, "loop"), 3)
    with Router(engine, n_replicas=2, plan=plan, retry_budget=4,
                watchdog_s=0.2, probe_interval_s=0.02,
                sched_kwargs={"harvest_lag": 4}) as router:
        router.run(traffic(), timeout_s=600)
        s = router.summary()
        evict = router.evict_log[0] if router.evict_log else {}
    lost = (s["fleet_requests_submitted"]
            - (s["fleet_requests_finished"] + s["fleet_requests_rejected"]
               + s["fleet_requests_expired"] + s["fleet_requests_failed"]
               + s["fleet_requests_aborted"]))
    row["failover"] = {
        "time_to_evict_s": evict.get("detect_latency_s"),
        "requests_retried": s["fleet_retries"],
        "requests_failed": s["fleet_requests_failed"],
        "requests_lost": lost,
        "evictions": s["fleet_evictions"],
        "restarts": s["fleet_restarts"],
    }
    return row


def bench_store_rpc(n_ops: int = 300) -> dict:
    """Store RPC microbench (ISSUE 13): per-verb latency of the
    control-plane store, local (``HostKVStore`` — a lock and a dict)
    vs TCP (``TCPStoreClient`` against a localhost
    ``TCPStoreServer`` — framing + a socket round trip).  The gap IS
    the price of a real multi-process control plane, and the number
    SCALING.md's heartbeat-period arithmetic divides by: a verb's p99
    must sit far under ``heartbeat_s`` or the liveness layer's beat
    thread falls behind its own lease."""
    from dtdl_tpu.obs.hist import LogHistogram
    from dtdl_tpu.parallel.kvstore import HostKVStore
    from dtdl_tpu.parallel.tcpstore import TCPStoreClient, TCPStoreServer

    def drive(store):
        hists = {v: LogHistogram() for v in ("set", "get", "add")}
        ops = {"set": lambda i: store.set(f"k{i % 32}", i),
               "get": lambda i: store.get(f"k{i % 32}", None),
               "add": lambda i: store.add("ctr")}
        for verb, h in hists.items():
            for i in range(n_ops):
                t0 = time.perf_counter()
                ops[verb](i)
                h.add(time.perf_counter() - t0)
        return {verb: h.summary(unit=1e6, digits=2)   # microseconds
                for verb, h in hists.items()}

    row = {"model": "store_rpc", "n_ops": n_ops}
    row["local"] = drive(HostKVStore())
    server = TCPStoreServer().start()
    try:
        row["tcp"] = drive(TCPStoreClient(server.addr))
    finally:
        server.stop()
    return row


def bench_elastic(n_workers: int = 4, steps: int = 12,
                  overhead_steps: int = 24, reps: int = 3,
                  backend: str = "host") -> dict:
    """Elastic-training row (ISSUE 12): the kill-one-of-N drill's MTTR
    decomposition plus the liveness-layer overhead receipt.

    ``backend`` selects the control-plane store (ISSUE 13): ``host``
    is the PR 12 in-process ``HostKVStore``; ``tcp`` runs the SAME
    drill through a localhost ``TCPStoreServer`` + per-world
    ``TCPStoreClient`` — the elastic_tcp row's MTTR sits beside the
    in-process one, so the cost of real sockets on the recovery path
    is a printed number, not a guess.

    Drill: ``n_workers`` thread-hosted ElasticWorkers train a tiny MLP
    through the host control-plane store; ``peer_site`` kills one
    mid-run.  Receipts decompose MTTR exactly as SCALING.md's failure
    model does: ``detect_s`` (victim death → first survivor's named
    PeerLostError; bounded by watchdog_s + a poll slice), ``reform_s``
    (abort → new-generation world formed), ``restore_s`` (world →
    committed snapshot restored), ``first_step_s`` (restore → first
    applied step of the shrunken world), and ``mttr_s`` = death → first
    new step.  ``samples_lost``/``samples_double_counted`` audit the
    effective timeline against the world-size-agnostic sampler and must
    both be ZERO.

    Overhead: the same 2-worker world with the heartbeat lease layer on
    vs off (interleaved best-of-``reps``); the liveness layer is
    host-threads-only — zero device syncs by construction — so
    ``liveness_overhead_frac`` must sit inside the obs <2% contract.
    """
    from dtdl_tpu.data.sharding import GlobalBatchSampler
    from dtdl_tpu.models import MLP
    from dtdl_tpu.parallel.kvstore import HostKVStore, RetryingStore
    from dtdl_tpu.parallel.tcpstore import TCPStoreClient, TCPStoreServer
    from dtdl_tpu.resil import (ElasticConfig, ElasticWorker, FaultPlan,
                                effective_sample_log, peer_site,
                                run_workers)
    from dtdl_tpu.train import init_state

    if backend not in ("host", "tcp"):
        raise ValueError(f"unknown store backend {backend!r}")
    servers = []

    def mk_store():
        if backend == "host":
            return HostKVStore()
        srv = TCPStoreServer().start()
        servers.append(srv)
        return TCPStoreClient(srv.addr)

    n_ex, dim, gbatch = 96, 16, 12
    rng = np.random.default_rng(0)
    x_all = rng.normal(size=(n_ex, dim)).astype(np.float32)
    y_all = rng.integers(0, 10, n_ex)
    model = MLP(n_units=8)
    state0 = init_state(model, jax.random.PRNGKey(0),
                        jnp.zeros((1, dim)), optax.sgd(0.1))

    def loss(p, b):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply({"params": p}, b["x"]), b["y"]).mean()

    grad_jit = jax.jit(lambda p, b: jax.grad(loss)(p, b))
    apply_jit = jax.jit(lambda s, g, n: s.apply_gradients(
        grads=jax.tree.map(lambda v: v / n, g)))
    grad_fn = lambda s, b: grad_jit(s.params, b)          # noqa: E731
    apply_fn = lambda s, g, n: apply_jit(s, g, float(n))  # noqa: E731
    batch_fn = lambda i: {"x": jnp.asarray(x_all[i]),     # noqa: E731
                          "y": jnp.asarray(y_all[i])}
    # warm the compiled step outside every timed region (a first-call
    # compile inside a worker reads as a wedge to the step deadline)
    apply_fn(state0, jax.device_get(grad_fn(state0,
                                            batch_fn(np.arange(4)))), 2)

    def mk_world(store, ranks, n_steps, cfg, ckpt_dir=None):
        sampler = GlobalBatchSampler(n_ex, gbatch, seed=3)
        return [ElasticWorker(
            RetryingStore(store), r, init_fn=lambda: state0,
            grad_fn=grad_fn, apply_fn=apply_fn, batch_fn=batch_fn,
            sampler=sampler, total_steps=n_steps, cfg=cfg,
            ckpt_dir=ckpt_dir, audit_samples=True) for r in ranks]

    row = {"model": "elastic" if backend == "host" else "elastic_tcp",
           "n_workers": n_workers, "steps": steps, "backend": backend}

    # ---- liveness-layer overhead: heartbeats on vs off ----------------
    def world_wall(heartbeat_s):
        cfg = ElasticConfig(heartbeat_s=heartbeat_s, watchdog_s=0.5,
                            step_timeout_s=30.0, join_grace_s=0.1,
                            snapshot_every=10 ** 9)
        ws = mk_world(mk_store(), list(range(2)), overhead_steps, cfg)
        t0 = time.perf_counter()
        run_workers(ws, timeout_s=300)
        assert all(w.done for w in ws)
        return time.perf_counter() - t0

    on = min(world_wall(0.02) for _ in range(reps))
    off = min(world_wall(0.0) for _ in range(reps))
    row["liveness"] = {
        "steps": overhead_steps,
        "wall_on_s": round(on, 4), "wall_off_s": round(off, 4),
        "steps_per_sec": round(overhead_steps / on, 1),
        "overhead_frac": round(max(0.0, 1.0 - off / on), 4),
    }

    # ---- the kill-one-of-N drill --------------------------------------
    cfg = ElasticConfig(heartbeat_s=0.02, watchdog_s=0.2,
                        step_timeout_s=5.0, join_grace_s=0.1,
                        snapshot_every=2)
    victim_rank, kill_at = n_workers - 2, steps // 2
    plan = FaultPlan().at(peer_site(victim_rank, "step"), kill_at,
                          "crash")
    store = mk_store()
    ckpt_dir = tempfile.mkdtemp(prefix="bench_elastic_")
    with plan:
        ws = mk_world(store, list(range(n_workers)), steps, cfg,
                      ckpt_dir=ckpt_dir)
        run_workers(ws, timeout_s=300)
    victim = ws[victim_rank]
    survivors = [w for w in ws if w.rank != victim_rank]
    assert all(w.done for w in survivors), "survivors must finish"

    def first(w, name, **match):
        for n, t, info in w.events:
            if n == name and all(info.get(k) == v
                                 for k, v in match.items()):
                return t
        return None

    detects = [first(w, "peer_lost") for w in survivors]
    worlds1 = [first(w, "world", generation=1) for w in survivors]
    restores = [first(w, "restore") for w in survivors]
    applied1 = [first(w, "applied", generation=1) for w in survivors]
    t_dead = victim.stopped_t
    detect = min(detects) - t_dead
    reform = max(worlds1) - min(detects)
    restore = max(restores) - max(worlds1)
    first_step = max(applied1) - max(restores)
    # sample-level accounting over what the workers ACTUALLY consumed
    # (audit_samples logs the fed shard indices): compare the effective
    # timeline's multiset against the sampler's pure stream per step
    eff = effective_sample_log(ws)
    sampler = GlobalBatchSampler(n_ex, gbatch, seed=3)
    lost = dups = 0
    for s in range(steps):
        want = Counter(sampler.batch_indices(s).tolist())
        got = Counter(eff[s].tolist()) if s in eff else Counter()
        lost += sum((want - got).values())
        dups += sum((got - want).values())
    row["drill"] = {
        "victim": victim_rank, "kill_at_step": kill_at,
        "world_after": len(survivors),
        "detect_s": round(detect, 4),
        "reform_s": round(reform, 4),
        "restore_s": round(restore, 4),
        "first_step_s": round(first_step, 4),
        "mttr_s": round(max(applied1) - t_dead, 4),
        "watchdog_s": cfg.watchdog_s,
        "samples_lost": lost,
        "samples_double_counted": dups,
    }
    for srv in servers:
        srv.stop()
    return row


def bench_obs_pipeline(n_requests: int = 24, new_tokens: int = 24,
                       reps: int = 4) -> dict:
    """Fleet-era observability receipt (ISSUE 11): the SAME serve
    traffic with the full pipeline off vs ON — request-correlated
    tracing (per-request events + flow markers), the continuous
    metrics exporter sampling window deltas at harvest/drain
    boundaries, and the SLO evaluator judging every sampled point.

    The contract is the PR-3 bar: ``overhead_frac`` (1 - on/off decode
    tokens/sec) stays under 2% with ZERO added per-token syncs — the
    pipeline touches host counters at request-lifecycle and boundary
    granularity only, never per token (structurally pinned by
    tests/test_obs_export.py re-running the compile-receipt suite with
    the pipeline on).  Driven through the single-threaded Scheduler so
    the measurement is the hot decode path, not thread-scheduling noise
    (the Router layer adds host work per REQUEST, measured separately
    in the fleet row); interleaved best-of-``reps`` against this box's
    ambient drift, like the robustness row."""
    import flax.linen as nn
    from dtdl_tpu.models import transformer_lm
    from dtdl_tpu.obs import (MetricsExporter, Observer, SLO,
                              SLOEvaluator)
    from dtdl_tpu.serve import InferenceEngine, Request, Scheduler

    model = transformer_lm("tiny", attn_impl="dense", dtype=jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    engine = InferenceEngine(model, params, n_slots=4, buckets=(64,))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size,
                            int(rng.integers(8, 64))).tolist()
               for _ in range(n_requests)]
    # warm the compiled programs outside every timed region
    Scheduler(engine, harvest_lag=4).run([Request(list(prompts[0]), 4)])

    def run_off():
        sched = Scheduler(engine, harvest_lag=4)
        t0 = time.perf_counter()
        sched.run([Request(list(p), new_tokens) for p in prompts])
        dt = time.perf_counter() - t0
        return sched.metrics.summary()["decode_tokens"] / dt, None

    def run_on():
        obs = Observer(trace=True, sentinel="warn")
        exporter = MetricsExporter(interval_s=0.05)
        exporter.attach_slo(SLOEvaluator([
            SLO("ttft_p99", metric="ttft_s_p99", op="<=", target=60.0),
            SLO("availability", good="requests_finished",
                bad=("requests_failed", "requests_expired"),
                target=0.999),
        ], observer=obs))
        sched = Scheduler(engine, harvest_lag=4, observer=obs,
                          exporter=exporter)
        t0 = time.perf_counter()
        sched.run([Request(list(p), new_tokens) for p in prompts])
        dt = time.perf_counter() - t0
        receipts = {
            "trace_events": len(obs.tracer),
            "export_snapshots": exporter.n_snapshots,
            **exporter.slo.summary(),
        }
        return sched.metrics.summary()["decode_tokens"] / dt, receipts

    best = {"off": 0.0, "on": 0.0}
    receipts = None
    run_off(), run_on()           # one warm lap each (allocator, trace)
    for _ in range(reps):
        tps, _ = run_off()
        best["off"] = max(best["off"], tps)
        tps, rec = run_on()
        if tps > best["on"]:
            best["on"], receipts = tps, rec
    return {"model": "obs_pipeline", "n_requests": n_requests,
            "new_tokens": new_tokens,
            "off_tokens_per_sec": round(best["off"], 1),
            "on_tokens_per_sec": round(best["on"], 1),
            "overhead_frac": round(1.0 - best["on"] / best["off"], 4),
            **(receipts or {})}


# ---------------------------------------------------------------------------
# modeled multi-chip scaling (SCALING.md)
#
# These curves are a MODEL, not a measurement: the inputs are the
# single-chip step time and the exact gradient byte volume every
# data-parallel replica must allreduce.  The model below turns those
# into 1->32-chip efficiency curves, with the interconnect constants
# documented as public-spec estimates.
# ---------------------------------------------------------------------------

# Effective allreduce bandwidth per chip over ICI (bytes/s).  v5e has a 2D
# torus with 4 ICI links/chip at ~45 GB/s each per direction; a
# bandwidth-optimal ring allreduce drives 2 links concurrently -> ~90 GB/s
# effective.  DCN: ~200 Gbps (25 GB/s) per host NIC, shared by the host's
# 8 chips; the hierarchical allreduce below accounts for the sharing.
ICI_ALLREDUCE_BW = 90e9
DCN_HOST_BW = 25e9
CHIPS_PER_HOST = 8
# fraction of the backward pass the grad allreduce can hide under (XLA
# overlaps collective-start with remaining backward compute, like DDP's
# bucketed hooks), and backward's share of step time (~2 of 3 passes)
OVERLAP_FRAC = 0.9
BWD_FRAC = 2 / 3


def _allreduce_time(nbytes: float, n: int, bw: float) -> float:
    """Ring/bidirectional-exchange allreduce: 2 * B * (N-1)/N / bw."""
    if n <= 1:
        return 0.0
    return 2.0 * nbytes * (n - 1) / n / bw


def modeled_scaling(step_time_s: float, grad_bytes: float,
                    chips=(1, 2, 4, 8, 16, 32)) -> dict:
    """DDP weak-scaling efficiency: fixed per-chip batch, grads allreduced.

    ``ici``: all chips in one ICI domain (a v5e pod slice).  ``hybrid``:
    8-chip ICI hosts joined over DCN — intra-host reduce-scatter/allgather
    leaves each chip 1/8 of the grads, the DCN stage moves that share
    through 1/8 of the host NIC, then the ICI stage finishes.  Exposed
    time is whatever the overlap window (OVERLAP_FRAC of the backward)
    cannot hide.  Efficiency = t_step / (t_step + exposed).
    """
    def eff(t_comm, overlap):
        window = OVERLAP_FRAC * BWD_FRAC * step_time_s if overlap else 0.0
        exposed = max(0.0, t_comm - window)
        return round(step_time_s / (step_time_s + exposed), 4)

    out = {"ici": {}, "hybrid": {}, "ici_no_overlap": {},
           "hybrid_no_overlap": {}, "comm_ms": {}}
    for n in chips:
        if n > CHIPS_PER_HOST and n % CHIPS_PER_HOST:
            raise ValueError(
                f"chips={n}: counts > {CHIPS_PER_HOST} must be whole hosts "
                f"(multiples of {CHIPS_PER_HOST}) — a partial host would be "
                f"silently dropped from the hybrid model")
        t_ici = _allreduce_time(grad_bytes, n, ICI_ALLREDUCE_BW)
        hosts = max(1, n // CHIPS_PER_HOST)
        t_hyb = _allreduce_time(grad_bytes, min(n, CHIPS_PER_HOST),
                                ICI_ALLREDUCE_BW)
        if hosts > 1:
            # per chip: grad_bytes/8 over its 1/8 share of the host NIC
            t_hyb += _allreduce_time(grad_bytes / CHIPS_PER_HOST, hosts,
                                     DCN_HOST_BW / CHIPS_PER_HOST)
        out["ici"][n] = eff(t_ici, overlap=True)
        out["hybrid"][n] = eff(t_hyb, overlap=True)
        # worst case: nothing hides (the reference's gloo-era regime)
        out["ici_no_overlap"][n] = eff(t_ici, overlap=False)
        out["hybrid_no_overlap"][n] = eff(t_hyb, overlap=False)
        out["comm_ms"][n] = {"ici": round(t_ici * 1e3, 3),
                             "hybrid": round(t_hyb * 1e3, 3)}
    return out


# point-to-point ICI bandwidth (one neighbor link, one direction) — the
# pp ppermute hops and the sp ring ride single links, unlike the
# 2-link ring allreduce above
ICI_P2P_BW = 45e9
# fraction of the sequence-parallel ring traffic NOT hidden under the
# per-chunk attention compute (the zigzag ring overlaps send/recv with
# block attention by construction; 0.5 = half the hops exposed is the
# conservative end measured for flash-block sizes on v5e-class chips)
RING_EXPOSED = 0.5


def modeled_scaling_4d(step_time_s: float, grad_bytes: float, *,
                       d_model: int, n_layers: int, batch: int, seq: int,
                       n_microbatches: int = 8, n_experts: int = 0,
                       capacity_factor: float = 1.25,
                       moe_every: int = 2,
                       meshes=((1, 1, 1, 1), (1, 1, 1, 2), (1, 1, 1, 4),
                               (1, 1, 1, 8), (1, 1, 2, 1), (1, 1, 4, 1),
                               (1, 2, 2, 2), (2, 2, 2, 2),
                               (1, 2, 2, 8))) -> dict:
    """Strong-scaling model for the 4D megatron path (SCALING.md).

    The DDP model above weak-scales a fixed per-chip batch; the 4D
    engine's purpose is the opposite — split ONE model/batch over a
    ('data','seq','pipe','model') mesh.  Per mesh (dp, sp, pp, tp):

    * compute: ``t_step / n`` (the measured single-chip step divided
      over all four axes), inflated by the segmented-1F1B bubble
      ``(pp-1) / (M + pp - 1)`` (the Megatron 1F1B bound at v=1 —
      megatron.bubble_fraction);
    * tp: 4 activation allreduces per owned layer (2 fwd + 2 bwd,
      Megatron column->row pairs) of the local [B/dp · S/sp, D] bf16
      activations over the tp group (ring-allreduce cost);
    * sp: the zigzag ring forwards each chip's K+V shard (sp-1) hops per
      owned layer, ~3x for the backward's re-ring + dKV ring, over
      single ICI links; ``RING_EXPOSED`` of it is not hidden under
      block-attention compute;
    * pp: each chip ppermutes every microbatch's boundary activations
      once forward and once backward (single-link p2p);
    * ep: routed MoE all-to-alls ``cf``-capacity token buffers to the
      expert shards over 'model' — 2 (dispatch+combine) x 2 (fwd+bwd),
      (tp-1)/tp of the tokens leave the chip — on every
      ``moe_every``-th layer;
    * dp: the grad allreduce of this chip's parameter shard
      (``grad_bytes / (pp·tp)`` f32), overlap-windowed like the DDP
      model.

    Efficiency = ideal linear time / modeled time; (1,1,1,1) is exactly
    the measured step (sanity anchor).  Constants: ICI_ALLREDUCE_BW,
    ICI_P2P_BW, RING_EXPOSED, OVERLAP_FRAC/BWD_FRAC above.
    """
    out = {}
    for dp, sp, pp, tp in meshes:
        n = dp * sp * pp * tp
        M = n_microbatches
        act_bytes = batch * seq * d_model * 2 / (dp * sp)   # bf16, local
        layers_owned = n_layers / pp

        bubble = (pp - 1) / (M + pp - 1) if pp > 1 else 0.0
        t_compute = step_time_s / n
        t_pipe = t_compute / (1.0 - bubble)

        t_tp = layers_owned * 4 * _allreduce_time(
            act_bytes, tp, ICI_ALLREDUCE_BW)
        # each of the (sp-1) ring rounds sends this chip's FULL K+V shard
        # (2 * act_bytes — act_bytes is already the per-chip slice, so no
        # (n-1)/n allreduce discount applies to p2p hops)
        t_sp = (RING_EXPOSED * layers_owned * 3 * 2 * act_bytes
                * (sp - 1) / ICI_P2P_BW) if sp > 1 else 0.0
        t_pp = (2 * act_bytes / ICI_P2P_BW) if pp > 1 else 0.0
        t_moe = 0.0
        if n_experts and tp > 1:
            moe_layers = layers_owned / moe_every
            t_moe = (moe_layers * 4 * capacity_factor * act_bytes
                     * (tp - 1) / tp / ICI_P2P_BW)
        dp_grad = _allreduce_time(grad_bytes / (pp * tp), dp,
                                  ICI_ALLREDUCE_BW)
        window = OVERLAP_FRAC * BWD_FRAC * t_pipe
        t_dp = max(0.0, dp_grad - window)

        t_total = t_pipe + t_tp + t_sp + t_pp + t_moe + t_dp
        out[f"{dp},{sp},{pp},{tp}"] = {
            "chips": n,
            "efficiency": round(t_compute / t_total, 4),
            "speedup": round(step_time_s / t_total, 2),
            "step_ms": round(t_total * 1e3, 3),
            "comm_ms": {"tp": round(t_tp * 1e3, 3),
                        "sp": round(t_sp * 1e3, 3),
                        "pp": round(t_pp * 1e3, 3),
                        "moe": round(t_moe * 1e3, 3),
                        "dp_exposed": round(t_dp * 1e3, 3)},
            "bubble": round(bubble, 4),
        }
    return out


def _grad_bytes(model, example) -> float:
    """f32 gradient bytes of one replica (flax keeps params f32 under
    bf16 compute; DDP allreduces full-precision grads).  Only the
    'params' collection counts: BatchNorm running stats are psum-averaged
    separately, not part of the gradient payload."""
    shapes = jax.eval_shape(
        lambda k: model.init(k, example), jax.random.PRNGKey(0))
    return float(sum(np.prod(l.shape) * 4
                     for l in jax.tree.leaves(shapes["params"])
                     if hasattr(l, "shape")))


def scaling_section(records) -> dict:
    """Modeled scaling curves for the headline rows of this bench run,
    plus the reference-sanity point (see SCALING.md)."""
    from dtdl_tpu.models import pyramidnet, resnet50, transformer_lm

    out = {}
    for r in records:
        if "step_time_ms" not in r:
            continue
        key = None
        if r["model"] == "pyramidnet" and r["batch_size"] == 256:
            key, model, ex = ("pyramidnet_bs256", pyramidnet(),
                              jnp.zeros((1, 32, 32, 3)))
        elif r["model"] == "resnet50" and r["batch_size"] == 256:
            key, model, ex = ("resnet50_bs256", resnet50(),
                              jnp.zeros((1, 224, 224, 3)))
        elif r["model"] == "lm" and r.get("size") in ("base", "large"):
            key, model, ex = (f"lm_{r['size']}_seq{r['seq']}",
                              transformer_lm(r["size"], max_seq=r["seq"]),
                              jnp.zeros((1, r["seq"]), jnp.int32))
        if key:
            gb = _grad_bytes(model, ex)
            out[key] = {"grad_mbytes": round(gb / 1e6, 1),
                        **modeled_scaling(r["step_time_ms"] / 1e3, gb)}
            if key.startswith("lm_"):
                # the 4D engine's strong-scaling model, anchored on the
                # same measured step (SCALING.md "The 4D model"); 'large'
                # shows the shape effect — bigger d_model amortizes the
                # tp activation psums over 4x the MXU work
                out[f"megatron_4d_{key[3:]}"] = modeled_scaling_4d(
                    r["step_time_ms"] / 1e3, gb,
                    d_model=model.d_model, n_layers=model.n_layers,
                    batch=r["batch_size"], seq=r["seq"])
    if out:
        # sanity anchor: solving the (no-overlap) model for the
        # reference's published 4-GPU point — PyramidNet, 0.255 s/step,
        # 75% efficiency (reference pytorch/README.md:122-125) — implies
        # an effective allreduce bandwidth of ~1.7 GB/s, plausible for
        # its unoverlapped gloo/PCIe-era allreduce; see SCALING.md
        if "pyramidnet_bs256" in out:   # same grads; skip the re-trace
            gb_ref = out["pyramidnet_bs256"]["grad_mbytes"] * 1e6
        else:
            gb_ref = _grad_bytes(pyramidnet(), jnp.zeros((1, 32, 32, 3)))
        t_ref, eff_ref = 0.255, 0.75
        exposed = t_ref / eff_ref - t_ref
        out["reference_4gpu_sanity"] = {
            "measured_eff": eff_ref,
            "implied_allreduce_gbps": round(
                2 * gb_ref * 3 / 4 / exposed / 1e9, 2),
        }
    return out


_SWEEP = {
    # headline (reference parity) model: sweep to find the throughput knee
    "pyramidnet": (64, 256, 1024),
    # north-star model (BASELINE.json): ImageNet shapes
    "resnet50": (64, 256),
    # long-context causal LM (flash attention) at seq 4096: 'small' is the
    # throughput row (1.1M tok/s), 'base'/'large' the MFU rows (d_model
    # 512/1024 feed the MXU properly — see LM_ROOFLINE.md; 'large' is the
    # roofline-cash row: 239M params at bs 4, no remat, dense head — the
    # measured-best config, see bench_lm's docstring)
    "lm": (8,),
}

_LM_SIZES = ("small", "base", "large", "base-moe8")
# per-size batch override for the sweep (explicit --batch-size wins):
# 'large' peaks at bs 4 — see bench_lm's docstring
_LM_BS = {"large": 4}


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="all",
                   choices=["all", "pyramidnet", "resnet50", "lm"])
    p.add_argument("--batch-size", type=int, default=0,
                   help="single batch size instead of the sweep")
    p.add_argument("--quick", action="store_true",
                   help="single config only (default pyramidnet bs=64; "
                        "honors explicit --model / --batch-size)")
    p.add_argument("--sample-budget", type=int, default=0,
                   help="override the per-config timed sample budget "
                        "(smoke tests on slow hosts; 0 = default)")
    p.add_argument("--records-file", default="bench_records.json",
                   help="where the full per-config records + scaling model "
                        "are written (the final stdout line stays compact)")
    p.add_argument("--lm-size", default="all",
                   choices=["all"] + list(_LM_SIZES),
                   help="restrict the LM rows to one size")
    p.add_argument("--skip-host-overhead", action="store_true",
                   help="skip the sync/async/unrolled host-overhead "
                        "microbench row")
    p.add_argument("--skip-serving", action="store_true",
                   help="skip the serving (prefill/decode tokens/sec vs "
                        "batch size) row")
    p.add_argument("--skip-fleet", action="store_true",
                   help="skip the serving-fleet row (1 vs 2 replica "
                        "Router throughput + kill-one-replica failover "
                        "drill)")
    p.add_argument("--skip-chunked", action="store_true",
                   help="skip the chunked-prefill interference row "
                        "(p99 inter-token latency with/without "
                        "chunking under mixed long-prompt traffic + "
                        "the disaggregated-fleet handoff receipt)")
    p.add_argument("--skip-kv-hierarchy", action="store_true",
                   help="skip the hierarchical KV cache row "
                        "(cold/HBM/host/disk TTFT per tier + the "
                        "fleet prefix-directory kill drill)")
    p.add_argument("--skip-observability", action="store_true",
                   help="skip the observability-overhead (tracer on vs "
                        "off steps/sec) row")
    p.add_argument("--skip-multitenant", action="store_true",
                   help="skip the multi-tenant serving row (batched "
                        "multi-LoRA, grammar-constrained decode, token "
                        "streaming — round 22)")
    p.add_argument("--skip-elastic", action="store_true",
                   help="skip the elastic-training row (kill-one-of-N "
                        "MTTR drill + liveness-layer overhead)")
    p.add_argument("--skip-elastic-tcp", action="store_true",
                   help="skip the TCP-backed elastic row (the same "
                        "kill-one-of-N MTTR drill through a localhost "
                        "TCPStoreServer instead of the in-process "
                        "store)")
    p.add_argument("--skip-store-rpc", action="store_true",
                   help="skip the control-plane store RPC microbench "
                        "(local vs TCP per-verb latency)")
    p.add_argument("--skip-obs-pipeline", action="store_true",
                   help="skip the serve observability-pipeline row "
                        "(correlated tracing + exporter + SLO eval on "
                        "vs off decode tokens/sec)")
    p.add_argument("--skip-robustness", action="store_true",
                   help="skip the robustness (resil step guard on vs off "
                        "steps/sec) row")
    p.add_argument("--skip-audit", action="store_true",
                   help="skip the program-shape audit row (pinned "
                        "train/megatron/decode/verify collective census "
                        "+ donated bytes vs the checked-in baseline)")
    p.add_argument("--serve-size", default=None,
                   help="LM size for the serving row (default: tiny on "
                        "CPU, base on an accelerator)")
    p.add_argument("--skip-paged-kernel", action="store_true",
                   help="skip the isolated paged-attend microbench "
                        "(dense vs gather vs Pallas paged kernel)")
    p.add_argument("--skip-kernels", action="store_true",
                   help="skip the kernel microbench row (attention "
                        "old-vs-new fwd+bwd + sort vs sortless sampling)")
    p.add_argument("--kernel-seqs", default="4096",
                   help="comma-separated attention seq lengths for the "
                        "kernels row (default 4096; pass 4096,32768 on "
                        "a real TPU — 32k under the CPU interpreter "
                        "takes minutes per iteration)")
    p.add_argument("--kernel-iters", type=int, default=2,
                   help="timed iterations per kernels-row config")
    a = p.parse_args(argv)
    enable_compile_cache()

    if a.quick:
        # --quick narrows to ONE config but respects explicit choices
        # (it used to silently override --model/--batch-size).
        model = a.model if a.model != "all" else "pyramidnet"
        configs = [(model, a.batch_size or _SWEEP[model][0])]
    elif a.batch_size:
        models = _SWEEP.keys() if a.model == "all" else [a.model]
        configs = [(m, a.batch_size) for m in models]
    else:
        models = _SWEEP.keys() if a.model == "all" else [a.model]
        configs = [(m, bs) for m in models for bs in _SWEEP[m]]

    kind = getattr(jax.devices()[0], "device_kind", jax.devices()[0].platform)
    peak = peak_flops_per_chip()
    print(f"device: {kind} x{jax.device_count()}  "
          f"peak_bf16: {peak / 1e12 if peak else float('nan'):.0f} TFLOP/s",
          file=sys.stderr, flush=True)

    records = []
    # --quick keeps its one-config contract: a single LM size, not the set
    if a.lm_size != "all":
        lm_sizes = (a.lm_size,)
    else:
        lm_sizes = (_LM_SIZES[:1] if a.quick else _LM_SIZES)
    for model_name, sweep_bs in configs:
        sizes = lm_sizes if model_name == "lm" else (None,)
        for size in sizes:
            bs = sweep_bs
            if model_name == "lm" and not a.batch_size:
                bs = _LM_BS.get(size, sweep_bs)
            try:
                if model_name == "lm":
                    # budget caps the timed LM iterations too (floor 3)
                    lm_iters = (max(3, a.sample_budget // bs)
                                if a.sample_budget else 30)
                    row = bench_lm(bs, size=size, iters=lm_iters)
                else:
                    row = bench_one(model_name, bs,
                                    sample_budget=a.sample_budget or None)
            except Exception as e:  # e.g. OOM at a large batch — record it
                row = {"model": model_name, "batch_size": bs,
                       "error": f"{type(e).__name__}: {e}"[:200]}
                if size:
                    row["size"] = size
            records.append(row)
            print("  " + json.dumps(row), file=sys.stderr, flush=True)

    host_row = None
    if not a.skip_host_overhead:
        # host-overhead receipt: sync-every-step vs async-drain vs unrolled
        # dispatch through the SAME train_epoch loop (tiny model, so the
        # loop's host↔device stalls dominate) — see SCALING.md
        try:
            host_row = bench_host_overhead(
                steps=max(48, a.sample_budget // 64) if a.sample_budget
                else 192)
        except Exception as e:   # the microbench must never sink the bench
            host_row = {"model": "host_overhead",
                        "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(host_row)
        print("  " + json.dumps(host_row), file=sys.stderr, flush=True)

    obs_row = None
    if not a.skip_observability:
        # observability-overhead receipt: tracer+sentinel+goodput on vs
        # off through the same async train_epoch (<2% contract, ISSUE 3)
        try:
            obs_row = bench_observability(
                steps=max(48, a.sample_budget // 64) if a.sample_budget
                else 192)
        except Exception as e:   # the obs row must never sink the bench
            obs_row = {"model": "observability",
                       "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(obs_row)
        print("  " + json.dumps(obs_row), file=sys.stderr, flush=True)

    obs_pipe_row = None
    if not a.skip_obs_pipeline:
        # serve observability-pipeline receipt: correlated tracing +
        # continuous exporter + SLO eval on vs off through the same
        # Scheduler traffic (<2% contract, ISSUE 11)
        try:
            obs_pipe_row = bench_obs_pipeline()
        except Exception as e:  # the obs row must never sink the bench
            obs_pipe_row = {"model": "obs_pipeline",
                            "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(obs_pipe_row)
        print("  " + json.dumps(obs_pipe_row), file=sys.stderr,
              flush=True)

    resil_row = None
    if not a.skip_robustness:
        # robustness receipt: the resil step guard folded into the
        # compiled step vs off through the same async train_epoch (<2%
        # contract, ISSUE 5 — same bar as the observability row)
        try:
            resil_row = bench_robustness(
                steps=max(24, a.sample_budget // 256) if a.sample_budget
                else 48)
        except Exception as e:   # the resil row must never sink the bench
            resil_row = {"model": "robustness",
                         "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(resil_row)
        print("  " + json.dumps(resil_row), file=sys.stderr, flush=True)

    audit_row = None
    if not a.skip_audit:
        # program-shape receipt (ISSUE 15): collective census + donated
        # bytes of the pinned programs, with named drift vs baseline
        try:
            audit_row = bench_audit()
        except Exception as e:  # the audit row must never sink the bench
            audit_row = {"model": "audit",
                         "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(audit_row)
        print("  " + json.dumps(audit_row), file=sys.stderr, flush=True)

    kern_row = None
    if not a.skip_kernels:
        # kernel-round receipt: attention fwd+bwd old (unfused rope,
        # hardcoded blocks) vs new (fused rope, autotune table) + the
        # decode sampling epilogue sorted vs sortless (ISSUE 8)
        try:
            kern_row = bench_kernels(
                seqs=tuple(int(s) for s in a.kernel_seqs.split(",")),
                iters=a.kernel_iters)
        except Exception as e:  # the kernels row must never sink the bench
            kern_row = {"model": "kernels",
                        "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(kern_row)
        print("  " + json.dumps(kern_row), file=sys.stderr, flush=True)

    pk_row = None
    if not a.skip_paged_kernel:
        # paged-attend microbench (kernel round 2): dense vs gather vs
        # the Pallas paged kernel at decode/verify widths, with the
        # HBM-bytes argument that is the TPU claim
        try:
            pk_row = bench_paged_kernel()
        except Exception as e:  # must never sink the bench
            pk_row = {"model": "paged_kernel",
                      "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(pk_row)
        print("  " + json.dumps(pk_row), file=sys.stderr, flush=True)

    serve_row = None
    if not a.skip_serving:
        # serving row: prefill vs decode tokens/sec vs batch size — the
        # first workload receipt of the serve/ subsystem (ISSUE 2)
        try:
            serve_row = bench_serving(size=a.serve_size)
        except Exception as e:  # the serving row must never sink the bench
            serve_row = {"model": "serving",
                         "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(serve_row)
        print("  " + json.dumps(serve_row), file=sys.stderr, flush=True)

    fleet_row = None
    if not a.skip_fleet:
        # fleet row: Router over thread-hosted replicas — 1 vs 2 replica
        # throughput + the kill-one-replica failover receipts (ISSUE 9)
        try:
            fleet_row = bench_fleet()
        except Exception as e:  # the fleet row must never sink the bench
            fleet_row = {"model": "fleet",
                         "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(fleet_row)
        print("  " + json.dumps(fleet_row), file=sys.stderr, flush=True)

    chunked_row = None
    if not a.skip_chunked:
        # chunked-prefill interference row (ISSUE 14): p99 inter-token
        # latency with/without chunking + the disagg handoff receipt
        try:
            chunked_row = bench_chunked_prefill()
        except Exception as e:  # the chunked row must never sink the bench
            chunked_row = {"model": "chunked_prefill",
                           "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(chunked_row)
        print("  " + json.dumps(chunked_row), file=sys.stderr, flush=True)

    kvh_row = None
    if not a.skip_kv_hierarchy:
        # hierarchical KV cache row (round 23): TTFT at every tier of
        # the spill hierarchy + the fleet prefix-directory kill drill
        try:
            kvh_row = bench_kv_hierarchy()
        except Exception as e:  # the kv row must never sink the bench
            kvh_row = {"model": "kv_hierarchy",
                       "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(kvh_row)
        print("  " + json.dumps(kvh_row), file=sys.stderr, flush=True)

    mt_row = None
    if not a.skip_multitenant:
        # multi-tenant row (round 22): N-adapter batching vs 1-adapter
        # vs base, grammar-constrained vs free decode, and the
        # streaming first-token gap
        try:
            mt_row = bench_multitenant()
        except Exception as e:  # must never sink the bench
            mt_row = {"model": "multitenant",
                      "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(mt_row)
        print("  " + json.dumps(mt_row), file=sys.stderr, flush=True)

    elastic_row = None
    if not a.skip_elastic:
        # elastic row: thread-hosted worker world — kill-one-of-N MTTR
        # decomposition + liveness-layer overhead receipt (ISSUE 12)
        try:
            elastic_row = bench_elastic()
        except Exception as e:  # the elastic row must never sink the bench
            elastic_row = {"model": "elastic",
                           "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(elastic_row)
        print("  " + json.dumps(elastic_row), file=sys.stderr, flush=True)

    elastic_tcp_row = None
    if not a.skip_elastic_tcp:
        # the SAME drill through real sockets (ISSUE 13): TCP-backed
        # MTTR beside the in-process row
        try:
            elastic_tcp_row = bench_elastic(backend="tcp")
        except Exception as e:  # must never sink the bench
            elastic_tcp_row = {"model": "elastic_tcp",
                               "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(elastic_tcp_row)
        print("  " + json.dumps(elastic_tcp_row), file=sys.stderr,
              flush=True)

    store_rpc_row = None
    if not a.skip_store_rpc:
        # store RPC microbench (ISSUE 13): local vs TCP verb latency
        try:
            store_rpc_row = bench_store_rpc()
        except Exception as e:  # must never sink the bench
            store_rpc_row = {"model": "store_rpc",
                             "error": f"{type(e).__name__}: {e}"[:200]}
        records.append(store_rpc_row)
        print("  " + json.dumps(store_rpc_row), file=sys.stderr,
              flush=True)

    ok = [r for r in records if "samples_per_sec" in r]
    # headline = the best-MFU row of the reference-parity model (pyramidnet),
    # so vs_baseline stays an apples-to-apples per-sample ratio against the
    # P100 PyramidNet number and the metric name is stable run-to-run; on
    # devices without an MFU estimate (CPU) the best-throughput row wins.
    # All rows, including the reference bs=64 config, stay in "records".
    pyr = [r for r in ok if r["model"] == "pyramidnet"] or ok
    head = (max(pyr, key=lambda r: (r.get("mfu", 0.0), r["samples_per_sec"]))
            if pyr else None)
    if head is None:
        # total failure: the per-config error rows still go to the records
        # file so the artifact says WHICH config failed and how
        fail = {"metric": "bench_failed", "value": 0,
                "unit": "samples/sec", "vs_baseline": 0}
        try:
            with open(a.records_file, "w") as f:
                json.dump({**fail, "records": records}, f, indent=1)
            fail["records_file"] = a.records_file
        except OSError as e:
            print(f"records file not written: {e}", file=sys.stderr)
        print(json.dumps(fail), flush=True)
        raise SystemExit(1)

    best = max(ok, key=lambda r: r["samples_per_sec"])
    names = {"pyramidnet": "pyramidnet110_cifar10",
             "resnet50": "resnet50_imagenet",
             "lm": f"lm_{head.get('size', 'small')}_seq{head.get('seq')}"}
    # summary = the compact scalars-only final stdout line; full = summary
    # plus the per-config records and the modeled scaling section, written
    # to --records-file and stderr (round 4 lost its bench artifact to a
    # truncated stdout line — the driver captures only a tail window)
    summary = {
        "metric": (f"{names[head['model']]}"
                   f"_train_samples_per_sec_bs{head['batch_size']}"),
        "value": head["samples_per_sec"],
        "unit": "samples/sec",
        # null (not 0.0) when no reference baseline applies to the headline
        # model, so consumers don't read "no baseline" as "0x regression"
        "vs_baseline": head.get("vs_baseline"),
        "device": kind,
    }
    if "mfu" in head:
        summary["mfu"] = head["mfu"]
    rn = [r for r in ok if r["model"] == "resnet50"]
    if rn:
        rbest = max(rn, key=lambda r: r["samples_per_sec"])
        summary["resnet50_samples_per_sec"] = rbest["samples_per_sec"]
        if "mfu" in rbest:
            summary["resnet50_mfu"] = rbest["mfu"]
    lm = [r for r in ok if r["model"] == "lm"]
    if lm:
        # throughput and MFU headline may come from different LM sizes
        # ('small' wins tokens/sec, 'base'/'large' win MFU) — report each
        lbest = max(lm, key=lambda r: r.get("tokens_per_sec", 0))
        summary["lm_tokens_per_sec"] = lbest.get("tokens_per_sec")
        with_mfu = [r for r in lm if "mfu" in r]
        if with_mfu:
            lm_mfu_best = max(with_mfu, key=lambda r: r["mfu"])
            summary["lm_mfu"] = lm_mfu_best["mfu"]
            summary["lm_mfu_size"] = lm_mfu_best.get("size")
    if host_row and "async_speedup_vs_sync" in host_row:
        summary["host_overhead_async_speedup"] = \
            host_row["async_speedup_vs_sync"]
    if obs_row and "overhead_frac" in obs_row:
        summary["observability_overhead_frac"] = obs_row["overhead_frac"]
    if obs_pipe_row and "overhead_frac" in obs_pipe_row:
        summary["obs_pipeline_overhead_frac"] = \
            obs_pipe_row["overhead_frac"]
        summary["obs_pipeline_tokens_per_sec"] = \
            obs_pipe_row["on_tokens_per_sec"]
        summary["obs_export_snapshots"] = \
            obs_pipe_row.get("export_snapshots")
        summary["slo_breach_events"] = \
            obs_pipe_row.get("slo_breach_events")
        summary["slo_burn_crossings"] = \
            obs_pipe_row.get("slo_burn_crossings")
    if resil_row and "overhead_frac" in resil_row:
        summary["robustness_overhead_frac"] = resil_row["overhead_frac"]
    if audit_row and "drift_findings" in audit_row:
        # program-shape drift: 0 = the compiled hot paths still match
        # the checked-in census baseline (collectives, donation, zero
        # host traffic) — the ISSUE 15 regression harness
        summary["audit_drift_findings"] = audit_row["drift_findings"]
        summary["audit_decode_host_transfers"] = \
            audit_row["serve_decode"]["host_transfers"]
        summary["audit_train_donated_bytes"] = \
            audit_row["train_step"]["donated_bytes"]
        summary["audit_train_allreduces"] = \
            audit_row["train_step"]["collectives_hlo"].get(
                "all-reduce", 0)
    if kern_row and kern_row.get("attention"):
        # kernel receipt: the largest-seq head_dim-128 entry is the one
        # the roofline story hangs on; fall back to whatever ran
        ka = kern_row["attention"]
        best_a = max(ka, key=lambda e: (e["head_dim"] == 128, e["seq"]))
        summary["kernel_attn_speedup"] = best_a["speedup"]
        summary["kernel_attn_tflops"] = best_a["new_tflops"]
        summary["kernel_attn_seq"] = best_a["seq"]
    if kern_row and kern_row.get("sampling"):
        ks = max(kern_row["sampling"], key=lambda e: e["vocab"])
        summary["sampling_sortless_speedup"] = ks["speedup"]
        summary["sampling_sortless_us"] = ks["sortless_us"]
        summary["sampling_vocab"] = ks["vocab"]
    if pk_row and pk_row.get("rows"):
        # paged-kernel receipt (kernel round 2): the decode-width row's
        # HBM-bytes ratio is the TPU claim; the ms columns are honest
        # but interpreter-bound on CPU (interpret flag says which)
        pk_d = next((r for r in pk_row["rows"] if r["s_new"] == 1),
                    pk_row["rows"][0])
        summary["kernel_paged_bytes_x"] = pk_d["bytes_x"]
        summary["kernel_paged_ms"] = pk_d["kernel_ms"]
        summary["kernel_paged_gather_ms"] = pk_d["gather_ms"]
        summary["kernel_paged_interpret"] = pk_row["interpret"]
    if serve_row and serve_row.get("sweep"):
        best_d = max(serve_row["sweep"],
                     key=lambda s: s["decode_tokens_per_sec"])
        summary["serve_decode_tokens_per_sec"] = \
            best_d["decode_tokens_per_sec"]
        summary["serve_prefill_tokens_per_sec"] = max(
            s["prefill_tokens_per_sec"] for s in serve_row["sweep"])
    if serve_row and serve_row.get("spec"):
        # spec-decode receipt: best greedy spec config vs the k=0
        # baseline through the same scheduler (ISSUE 4 acceptance)
        greedy = [e for e in serve_row["spec"] if e["temperature"] == 0.0]
        base = next((e for e in greedy if e["k"] == 0), None)
        spec = [e for e in greedy if e["k"] > 0]
        if base and spec:
            best_s = max(spec, key=lambda e: e["decode_tokens_per_sec"])
            summary["serve_spec_tokens_per_sec"] = \
                best_s["decode_tokens_per_sec"]
            summary["serve_spec_acceptance_rate"] = \
                best_s["acceptance_rate"]
            summary["serve_spec_speedup"] = round(
                best_s["decode_tokens_per_sec"]
                / base["decode_tokens_per_sec"], 3) \
                if base["decode_tokens_per_sec"] else None
    if serve_row and serve_row.get("paged"):
        # paged-arena receipt: prefix-cache hits measured on repeated-
        # system-prompt traffic, TTFT vs the dense row (ISSUE 6)
        rows = {e["arena"]: e for e in serve_row["paged"]}
        pp, dense = rows.get("paged+prefix"), rows.get("dense")
        if pp and dense:
            summary["serve_paged_tokens_per_sec"] = \
                pp["decode_tokens_per_sec"]
            summary["serve_prefix_hit_rate"] = pp["prefix_hit_rate"]
            summary["serve_prefix_ttft_vs_dense"] = round(
                pp["ttft_s_mean"] / dense["ttft_s_mean"], 3) \
                if dense["ttft_s_mean"] else None
    if serve_row and serve_row.get("quant"):
        # quantization receipt (ISSUE 7): measured tokens/sec per config
        # plus the byte receipts that ARE the TPU speedup (decode is
        # HBM-BW-bound; CPU timings here pay the dequant as compute)
        rows = {(e["arena"], e["weights"]): e
                for e in serve_row["quant"]}
        f32d, w8kv8d = rows.get(("dense", "f32")), \
            rows.get(("dense", "w8kv8"))
        if f32d and w8kv8d:
            summary["serve_quant_tokens_per_sec"] = \
                w8kv8d["decode_tokens_per_sec"]
            summary["serve_quant_speedup_vs_f32"] = round(
                w8kv8d["decode_tokens_per_sec"]
                / f32d["decode_tokens_per_sec"], 3) \
                if f32d["decode_tokens_per_sec"] else None
            summary["serve_quant_bytes_per_token"] = \
                w8kv8d["bytes_per_token"]
            summary["serve_quant_bytes_per_token_f32"] = \
                f32d["bytes_per_token"]
            summary["serve_quant_param_bytes_ratio"] = round(
                f32d["param_bytes"] / w8kv8d["param_bytes"], 3)
            summary["serve_quant_kv_arena_ratio"] = round(
                f32d["kv_arena_bytes"] / w8kv8d["kv_arena_bytes"], 3)
        f32p, w8kv8p = rows.get(("paged", "f32")), \
            rows.get(("paged", "w8kv8"))
        if f32p and w8kv8p and f32p["n_pages"]:
            summary["serve_quant_paged_capacity_x"] = round(
                w8kv8p["n_pages"] / f32p["n_pages"], 3)
        # fp8 receipt (kernel round 2): bytes/token strictly below the
        # int8 row — one-byte payloads with bf16 (not f32) scale
        # sidecars and fp8 weight matmuls
        fp8d = rows.get(("dense", "w8fkvf8"))
        if fp8d and w8kv8d:
            summary["serve_fp8_tokens_per_sec"] = \
                fp8d["decode_tokens_per_sec"]
            summary["serve_fp8_bytes_per_token"] = \
                fp8d["bytes_per_token"]
            summary["serve_fp8_vs_int8_bytes_x"] = round(
                w8kv8d["bytes_per_token"] / fp8d["bytes_per_token"], 3) \
                if fp8d["bytes_per_token"] else None
        fp8p = rows.get(("paged", "w8fkvf8"))
        if fp8p and f32p and f32p["n_pages"]:
            summary["serve_fp8_paged_capacity_x"] = round(
                fp8p["n_pages"] / f32p["n_pages"], 3)
    if fleet_row and fleet_row.get("replicas"):
        # fleet receipt (ISSUE 9): per-replica-count throughput plus
        # the failover drill — requests_lost MUST report 0
        by_n = {e["n_replicas"]: e for e in fleet_row["replicas"]}
        if 1 in by_n:
            summary["fleet_tokens_per_sec_1r"] = \
                by_n[1]["decode_tokens_per_sec"]
        if 2 in by_n:
            summary["fleet_tokens_per_sec_2r"] = \
                by_n[2]["decode_tokens_per_sec"]
        if 1 in by_n and 2 in by_n and by_n[1]["decode_tokens_per_sec"]:
            summary["fleet_speedup_2r"] = round(
                by_n[2]["decode_tokens_per_sec"]
                / by_n[1]["decode_tokens_per_sec"], 3)
        fo = fleet_row.get("failover") or {}
        summary["fleet_time_to_evict_s"] = fo.get("time_to_evict_s")
        summary["fleet_requests_retried"] = fo.get("requests_retried")
        summary["fleet_requests_lost"] = fo.get("requests_lost")

    if chunked_row and "error" not in chunked_row:
        # chunked-prefill receipt (ISSUE 14): the decoders' inter-token
        # p99 with a whole-prompt prefill landing mid-run vs the same
        # traffic chunked, token-identity asserted; plus the
        # disaggregated-fleet migration receipt
        summary["serve_chunked_p99_tok_latency_s"] = \
            chunked_row["chunked"]["p99_tok_latency_s"]
        summary["serve_chunked_p99_whole_s"] = \
            chunked_row["whole"]["p99_tok_latency_s"]
        summary["serve_chunked_p99_improvement_x"] = \
            chunked_row["p99_improvement_x"]
        summary["serve_chunked_token_identical"] = \
            chunked_row["token_identical"]
        dis = chunked_row.get("disagg") or {}
        summary["fleet_disagg_token_identical"] = \
            dis.get("token_identical")
        summary["fleet_disagg_migrations"] = dis.get("migrations")
        summary["fleet_disagg_kv_handoff_pages"] = \
            dis.get("kv_handoff_pages")
        summary["fleet_disagg_kv_handoff_s_mean"] = \
            dis.get("kv_handoff_s_mean")

    if mt_row and "error" not in mt_row:
        # multi-tenant receipts (round 22): N-adapter batching keeps
        # throughput (the non-split-batch claim), constrained decode's
        # host-mask tax, and the streamed-first-token gap
        lo, gr, st = mt_row["lora"], mt_row["grammar"], mt_row["stream"]
        summary["serve_lora_tokens_per_sec"] = \
            lo["n_adapters_tokens_per_sec"]
        summary["serve_lora_vs_base"] = round(
            lo["n_adapters_tokens_per_sec"] / lo["base_tokens_per_sec"],
            3) if lo["base_tokens_per_sec"] else None
        summary["serve_lora_vs_one_adapter"] = round(
            lo["n_adapters_tokens_per_sec"]
            / lo["one_adapter_tokens_per_sec"], 3) \
            if lo["one_adapter_tokens_per_sec"] else None
        summary["serve_grammar_tokens_per_sec"] = \
            gr["constrained_tokens_per_sec"]
        summary["serve_grammar_vs_free"] = round(
            gr["constrained_tokens_per_sec"] / gr["free_tokens_per_sec"],
            3) if gr["free_tokens_per_sec"] else None
        summary["serve_stream_ttfst_s"] = st["ttfst_s_mean"]
        summary["serve_stream_ttft_s"] = st["ttft_s_mean"]

    if elastic_row and "error" not in elastic_row:
        dr = elastic_row.get("drill") or {}
        summary["elastic_detect_s"] = dr.get("detect_s")
        summary["elastic_reform_s"] = dr.get("reform_s")
        summary["elastic_restore_s"] = dr.get("restore_s")
        summary["elastic_mttr_s"] = dr.get("mttr_s")
        summary["elastic_samples_lost"] = dr.get("samples_lost")
        summary["elastic_samples_double_counted"] = \
            dr.get("samples_double_counted")
        lv = elastic_row.get("liveness") or {}
        summary["elastic_liveness_overhead_frac"] = \
            lv.get("overhead_frac")

    if elastic_tcp_row and "error" not in elastic_tcp_row:
        dr = elastic_tcp_row.get("drill") or {}
        summary["elastic_tcp_detect_s"] = dr.get("detect_s")
        summary["elastic_tcp_reform_s"] = dr.get("reform_s")
        summary["elastic_tcp_restore_s"] = dr.get("restore_s")
        summary["elastic_tcp_mttr_s"] = dr.get("mttr_s")
        summary["elastic_tcp_samples_lost"] = dr.get("samples_lost")
        summary["elastic_tcp_samples_double_counted"] = \
            dr.get("samples_double_counted")

    if store_rpc_row and "error" not in store_rpc_row:
        for backend in ("local", "tcp"):
            verbs = store_rpc_row.get(backend) or {}
            get = verbs.get("get") or {}
            summary[f"store_rpc_{backend}_get_p50_us"] = get.get("p50")
            summary[f"store_rpc_{backend}_get_p99_us"] = get.get("p99")

    full = dict(summary)
    full["records"] = records
    full["best"] = {"model": best["model"], "batch_size": best["batch_size"],
                    "samples_per_sec": best["samples_per_sec"]}
    try:
        scaling = scaling_section(ok)
        if scaling:
            full["scaling"] = scaling
    except Exception as e:   # modeled section must never sink the bench
        print(f"scaling section failed: {e}", file=sys.stderr)
    try:
        with open(a.records_file, "w") as f:
            json.dump(full, f, indent=1)
        summary["records_file"] = a.records_file
    except OSError as e:     # unwritable cwd must never sink the bench
        print(f"records file not written: {e}", file=sys.stderr)
    print("full result: " + json.dumps(full), file=sys.stderr, flush=True)

    print(json.dumps(summary), flush=True)
    return full


if __name__ == "__main__":
    main()
