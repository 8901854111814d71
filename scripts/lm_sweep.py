"""LM step-time sweep for the roofline analysis (round 4).

Times the causal-LM train step across (size, bs, seq, vocab-chunk)
configs on the real chip, and compares XLA cost_analysis FLOPs against
an analytic matmul-FLOP count — cost_analysis cannot see inside Pallas
kernels, so the flash-attention FLOPs are missing from the reported MFU.
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from bench import lm_analytic_flops, peak_flops_per_chip
from dtdl_tpu.models import transformer_lm
from dtdl_tpu.parallel import choose_strategy
from dtdl_tpu.train import init_state, make_lm_train_step


def bench(size, bs, seq, chunk, remat=None, iters=30, warmup=5):
    strategy = choose_strategy("auto")
    overrides = {} if remat is None else {"remat": remat}
    model = transformer_lm(size, max_seq=seq, **overrides)
    tx = optax.adamw(3e-4)
    state = strategy.replicate(init_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32), tx))
    step = make_lm_train_step(strategy, vocab_chunk_size=chunk)
    rng = np.random.default_rng(0)
    batches = [strategy.shard_batch({
        "tokens": jnp.asarray(
            rng.integers(0, model.vocab_size, (bs, seq)), jnp.int32),
    }) for _ in range(4)]
    compiled = step.lower(state, batches[0]).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    xla_flops = float(ca.get("flops") or 0)

    for i in range(warmup):
        state, m = compiled(state, batches[i % 4])
    float(m["loss"])
    t0 = time.perf_counter()
    for i in range(iters):
        state, m = compiled(state, batches[i % 4])
    loss = float(m["loss"])
    dt = time.perf_counter() - t0
    assert np.isfinite(loss)
    step_ms = 1e3 * dt / iters
    af = lm_analytic_flops(model, bs, seq)
    peak = peak_flops_per_chip()
    row = {
        "size": size, "bs": bs, "seq": seq, "chunk": chunk,
        "remat": model.remat,
        "step_ms": round(step_ms, 3),
        "tokens_per_sec": round(bs * (seq - 1) * iters / dt, 0),
        "xla_flops": xla_flops, "analytic_flops": af,
    }
    if peak:   # None only on the cpu platform: no MFU there
        row["mfu_xla"] = round(xla_flops * iters / dt / peak, 4)
        row["mfu_analytic"] = round(af * iters / dt / peak, 4)
    return row


if __name__ == "__main__":
    configs = [
        ("small", 8, 4096, 0),
        ("small", 32, 4096, 4096),
        ("base", 8, 4096, 0),
        ("base", 16, 4096, 4096),
        ("base", 32, 4096, 4096),
        ("base", 32, 2048, 4096),
        # round-5 'large' sweep (LM_ROOFLINE.md §6): remat off fits at
        # bs 4 and wins; the preset default (remat=True) shown at bs 8
        ("large", 4, 4096, 0, False),
        ("large", 4, 4096, 4096, False),
        ("large", 8, 4096, 4096, False),
        ("large", 8, 4096, 4096, True),
        # long-context rows (LM_ROOFLINE.md §7): MFU holds flat as seq
        # doubles/quadruples at fixed tokens-per-step — the O(seq) flash
        # memory bound in action
        ("base", 4, 8192, 0, False),
        ("base", 2, 16384, 4096, False),
        ("base", 1, 32768, 4096, False),
        ("large", 2, 8192, 0, False),
    ]
    if len(sys.argv) > 1 and sys.argv[1] == "--size":
        if len(sys.argv) < 3:
            raise SystemExit("--size needs a value (small/base/large)")
        configs = [c for c in configs if c[0] == sys.argv[2]]
        if not configs:
            raise SystemExit(f"no sweep configs for size {sys.argv[2]!r}")
    elif len(sys.argv) > 1:
        idx = [int(x) for x in sys.argv[1].split(",")]
        configs = [configs[i] for i in idx]
    for c in configs:
        try:
            row = bench(*c)
        except Exception as e:
            row = {"size": c[0], "bs": c[1], "seq": c[2], "chunk": c[3],
                   "error": f"{type(e).__name__}: {e}"[:200]}
        print(json.dumps(row), flush=True)
