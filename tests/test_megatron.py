"""4D-parallel (dp x sp x pp x tp + ep) train step vs a plain jnp oracle.

The strongest distributed-correctness check in the suite (SURVEY §4: psum /
sharding equivalence on the fake CPU mesh): the full sharded pipeline step
must produce the same loss and the same parameter update as an unsharded
single-device re-implementation of the identical math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dtdl_tpu.ops.attention import mha_reference
from dtdl_tpu.ops.rope import apply_rope, rope_frequencies
from dtdl_tpu.parallel import megatron as M


# Sharded-step-vs-oracle parameter tolerance: the updates agree to 2e-4.
# Kept tight on purpose — a real semantic divergence (wrong collective,
# wrong schedule order) must not hide inside a wide allowance.
PARAM_TOL = dict(atol=2e-4, rtol=2e-4)
# same-engine resume-equivalence comparisons are bitwise up to 1e-6 (a
# restore bug must not hide under the oracle tolerance)
LOSS_RTOL = 1e-6
CKPT_PARAM_TOL = dict(rtol=1e-6)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, d_ff=64,
                n_stages=2, layers_per_stage=1, n_microbatches=2,
                max_seq=64, dtype=jnp.float32)
    base.update(kw)
    return M.MegatronConfig(**base)


def _batch(cfg, B=8, S=32, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "tokens": rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32),
        "mask": np.ones((B, S), np.float32),
    }


# ---- single-device oracle (same math, no sharding) -------------------------

def _rms(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            * scale)


def oracle_logits(cfg, params, tokens):
    """Unsharded forward to final LM-head logits; also returns the summed
    MoE balance aux (zero for dense) so oracle_loss shares this body."""
    emb = params["embed"]
    x = jnp.take(emb, tokens, axis=0)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq)
    b, s, d = x.shape
    M = cfg.n_microbatches
    mb = b // M
    aux_total = jnp.zeros((), jnp.float32)

    # layer order of the (interleaved) virtual pipeline: virtual stage
    # u = c*S + st runs device st's chunk-c rows; v=1 is plain stage-major
    vs = cfg.virtual_stages
    Lc = cfg.layers_per_stage // vs
    order = [(u % cfg.n_stages, (u // cfg.n_stages) * Lc + i)
             for u in range(vs * cfg.n_stages) for i in range(Lc)]
    for st, li in order:
        p = {k: v[st, li] for k, v in params["blocks"].items()}
        h = _rms(x, p["ln_attn"])

        def heads(w):
            y = jnp.einsum("bsd,dh->bsh", h, w)
            return y.reshape(b, s, cfg.n_heads,
                             cfg.head_dim).transpose(0, 2, 1, 3)
        q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        o = mha_reference(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = x + jnp.einsum("bsh,hd->bsd", o, p["wo"])

        h = _rms(x, p["ln_mlp"])
        if cfg.n_experts:
            logits = jnp.einsum("bsd,de->bse", h, p["router"])
            probs = jax.nn.softmax(logits, -1)
            idx = jnp.argmax(probs, -1)
            gate = jnp.max(probs, -1, keepdims=True)
            onehot = jax.nn.one_hot(idx, cfg.n_experts)
            # Switch aux per (microbatch, layer): the sharded step computes
            # f/p over each GLOBAL microbatch (psummed over data/seq/model)
            pm = probs.reshape(M, mb, s, cfg.n_experts)
            om = onehot.reshape(M, mb, s, cfg.n_experts)
            f = jnp.mean(om, axis=(1, 2))            # [M, E]
            pbar = jnp.mean(pm, axis=(1, 2))         # [M, E]
            aux_total = aux_total + cfg.n_experts * jnp.sum(
                jax.lax.stop_gradient(f) * pbar)
            xe = jnp.einsum("bse,bsd->ebsd", onehot, h)
            hh = jax.nn.silu(jnp.einsum("ebsd,edf->ebsf", xe, p["wg"])) \
                * jnp.einsum("ebsd,edf->ebsf", xe, p["wi"])
            y = jnp.einsum("ebsf,efd->bsd", hh, p["wo_mlp"])
            x = x + y * gate
        else:
            hh = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, p["wg"])) \
                * jnp.einsum("bsd,df->bsf", h, p["wi"])
            x = x + jnp.einsum("bsf,fd->bsd", hh, p["wo_mlp"])

    x = _rms(x, params["ln_f"])
    logits = jnp.einsum("bsd,vd->bsv", x, emb)
    return logits, aux_total


def oracle_loss(cfg, params, tokens, targets, mask):
    M = cfg.n_microbatches
    logits, aux_total = oracle_logits(cfg, params, tokens)
    lse = jax.nn.logsumexp(logits, -1)
    true_logit = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    ce = jnp.sum((lse - true_logit) * mask) / jnp.sum(mask)
    if cfg.n_experts:
        ce = ce + cfg.moe_aux_weight * aux_total / (cfg.n_layers * M)
    return ce


def oracle_eval(cfg, params, tokens, targets, mask):
    """Validation metrics of the same math: plain CE (no aux), token
    accuracy, both masked sums over every position."""
    logits, _ = oracle_logits(cfg, params, tokens)
    lse = jax.nn.logsumexp(logits, -1)
    true_logit = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    total = jnp.sum(mask)
    correct = jnp.sum((jnp.argmax(logits, -1) == targets) * mask)
    return {"loss": jnp.sum((lse - true_logit) * mask) / total,
            "accuracy": correct / total, "n_tokens": total}


# ---- tests -----------------------------------------------------------------

@pytest.mark.parametrize("n_experts,schedule,dispatch", [
    (0, "1f1b", "dense"), (4, "1f1b", "dense"), (0, "gpipe", "dense"),
    (4, "gpipe", "dense"), (4, "1f1b", "routed"), (4, "gpipe", "routed"),
])
def test_4d_step_matches_oracle(devices, n_experts, schedule, dispatch):
    # routed dispatch with capacity_factor == n_experts can never drop a
    # token, so it computes the identical function to the dense oracle
    cfg = _cfg(n_experts=n_experts, schedule=schedule, moe_dispatch=dispatch,
               capacity_factor=4.0)
    mesh = M.build_4d_mesh(devices)
    assert dict(mesh.shape) == {"data": 1, "seq": 2, "pipe": 2, "model": 2}

    params_host = M.init_params(cfg, jax.random.PRNGKey(0))
    batch_host = _batch(cfg)

    # oracle: loss + one plain-SGD update on unsharded params
    loss_ref, grads_ref = jax.value_and_grad(
        lambda p: oracle_loss(cfg, p, jnp.asarray(batch_host["tokens"]),
                              jnp.asarray(batch_host["targets"]),
                              jnp.asarray(batch_host["mask"])))(params_host)
    lr = 0.1
    params_ref = jax.tree.map(lambda p, g: p - lr * g, params_host, grads_ref)

    # sharded 4D step
    opt = optax.sgd(lr)
    params = M.place_params(mesh, cfg, params_host)
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    batch = M.shard_lm_batch(mesh, batch_host)
    params, opt_state, loss, metrics = step(
        params, opt_state, batch["tokens"], batch["targets"], batch["mask"])

    np.testing.assert_allclose(float(loss), float(loss_ref),
                               atol=1e-5, rtol=1e-5)
    if n_experts and dispatch == "routed":
        assert float(metrics["moe_dropped_frac"]) == 0.0
    flat_ref = jax.tree.leaves(params_ref)
    flat = jax.tree.leaves(jax.device_get(params))
    for a, b in zip(flat, flat_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **PARAM_TOL)


@pytest.mark.parametrize("n_experts,dispatch", [
    (0, "dense"), (4, "routed"),
])
def test_4d_eval_step_matches_oracle(devices, n_experts, dispatch):
    """make_megatron_eval_step == the unsharded oracle's validation
    metrics: plain CE (no MoE aux), token accuracy, mask-exact ragged
    tails — the 4D engine's restore-then-evaluate parity (reference
    tensorflow2/mnist_single.py:88-92, chainer/train_mnist_multi.py:101-104).
    """
    cfg = _cfg(n_experts=n_experts, moe_dispatch=dispatch,
               capacity_factor=4.0)
    mesh = M.build_4d_mesh(devices)
    params_host = M.init_params(cfg, jax.random.PRNGKey(0))
    batch_host = _batch(cfg)
    # ragged tails: whole-row padding and a mid-row cutoff must both be
    # excluded exactly from loss, accuracy, and the token count
    batch_host["mask"][:, -5:] = 0.0
    batch_host["mask"][0, 3:] = 0.0

    ref = oracle_eval(cfg, params_host, jnp.asarray(batch_host["tokens"]),
                      jnp.asarray(batch_host["targets"]),
                      jnp.asarray(batch_host["mask"]))

    eval_step = M.make_megatron_eval_step(cfg, mesh)
    params = M.place_params(mesh, cfg, params_host)
    batch = M.shard_lm_batch(mesh, batch_host)
    got = eval_step(params, batch["tokens"], batch["targets"],
                    batch["mask"])

    np.testing.assert_allclose(float(got["loss"]), float(ref["loss"]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(got["accuracy"]),
                               float(ref["accuracy"]), atol=1e-6)
    assert float(got["n_tokens"]) == float(ref["n_tokens"])
    # eval must not touch params (no donation, no update)
    got2 = eval_step(params, batch["tokens"], batch["targets"],
                     batch["mask"])
    assert float(got2["loss"]) == float(got["loss"])


@pytest.mark.slow
def test_4d_step_loss_decreases(devices):
    cfg = _cfg(n_experts=4)
    mesh = M.build_4d_mesh(devices)
    opt = optax.sgd(0.05, momentum=0.9)
    params = M.place_params(mesh, cfg, M.init_params(cfg, jax.random.PRNGKey(1)))
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    batch = M.shard_lm_batch(mesh, _batch(cfg, seed=1))
    losses = []
    for _ in range(5):
        params, opt_state, loss, metrics = step(
            params, opt_state, batch["tokens"], batch["targets"],
            batch["mask"])
        losses.append(float(loss))
        # routed is the default dispatch: drop accounting always reported
        assert 0.0 <= float(metrics["moe_dropped_frac"]) < 1.0
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(losses)), losses


def test_1f1b_more_microbatches_than_slots(devices):
    """M > 2S-1 exercises the ring reuse of the saved-activation slots."""
    cfg = _cfg(n_microbatches=8)
    mesh = M.build_4d_mesh(devices)
    batch_host = _batch(cfg, B=8, S=32, seed=2)
    params_host = jax.device_get(M.init_params(cfg, jax.random.PRNGKey(3)))
    loss_ref, grads_ref = jax.value_and_grad(
        lambda p: oracle_loss(cfg, p, jnp.asarray(batch_host["tokens"]),
                              jnp.asarray(batch_host["targets"]),
                              jnp.asarray(batch_host["mask"])))(params_host)
    params_ref = jax.tree.map(lambda p, g: p - 0.1 * g,
                              params_host, grads_ref)

    opt = optax.sgd(0.1)
    params = M.place_params(mesh, cfg, params_host)
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    batch = M.shard_lm_batch(mesh, batch_host)
    params, opt_state, loss, _ = step(params, opt_state, batch["tokens"],
                                      batch["targets"], batch["mask"])
    np.testing.assert_allclose(float(loss), float(loss_ref),
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(jax.device_get(params)),
                    jax.tree.leaves(params_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **PARAM_TOL)


def test_1f1b_single_device_mesh(devices):
    """tp=1 takes the replicated-head branch; S=1 degenerates the ring."""
    cfg = _cfg(n_stages=1, n_microbatches=4)
    mesh = M.build_4d_mesh(devices[:1])
    batch_host = _batch(cfg, B=8, S=32, seed=4)
    params_host = jax.device_get(M.init_params(cfg, jax.random.PRNGKey(5)))
    loss_ref = oracle_loss(cfg, params_host,
                           jnp.asarray(batch_host["tokens"]),
                           jnp.asarray(batch_host["targets"]),
                           jnp.asarray(batch_host["mask"]))
    opt = optax.sgd(0.1)
    params = M.place_params(mesh, cfg, params_host)
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    batch = M.shard_lm_batch(mesh, batch_host)
    _, _, loss, _ = step(params, opt_state, batch["tokens"],
                         batch["targets"], batch["mask"])
    np.testing.assert_allclose(float(loss), float(loss_ref),
                               atol=1e-5, rtol=1e-5)


def test_bubble_fraction():
    # segmented schedule: idle time = (S-1)(tf+tb)/v exactly when S | M —
    # the Megatron interleaved 1F1B bound (v=1: (S-1)/(M+S-1) fraction)
    assert M.bubble_fraction(_cfg(n_stages=1, n_microbatches=4)) == 0.0
    # S=2, M=2: total = 1*tf + 2*(tf+tb) + 1*tb = 9, ideal 6 -> 1/3
    assert abs(M.bubble_fraction(_cfg(n_stages=2, n_microbatches=2))
               - 1 / 3) < 1e-12
    # S=4, M=16: (S-1)/(M+S-1) = 3/19
    assert abs(M.bubble_fraction(_cfg(n_stages=4, n_microbatches=16))
               - 3 / 19) < 1e-12


def test_interleaved_tick_count_and_bubble_drop():
    """virtual_stages=v shrinks the idle fraction toward the 1/v bound
    (ticks stay chunk-sized: each costs 1/v of a stage)."""
    base = dict(n_stages=4, layers_per_stage=2, n_microbatches=8)
    v1 = _cfg(**base)
    v2 = _cfg(**base, virtual_stages=2)
    assert M.n_pipeline_ticks(v1) == 8 + 2 * 3          # M + 2(S-1)
    assert M.n_pipeline_ticks(v2) == 26                 # Mv + (v+1)S - 2
    # bubble TIME halves at v=2: (S-1)*3/v = 4.5 vs 9 stage-units
    b1, b2 = M.bubble_fraction(v1), M.bubble_fraction(v2)
    assert abs(b1 - 9 / 33) < 1e-12     # 9 idle of 24+9
    assert abs(b2 - 4.5 / 28.5) < 1e-12  # 4.5 idle of 24+4.5
    assert b2 < b1


@pytest.mark.parametrize("n_experts,virtual", [(0, 1), (0, 2), (4, 1)])
def test_to_flax_params_serves_4d_checkpoints(n_experts, virtual):
    """The serving bridge: megatron params converted to the flax tree
    compute the IDENTICAL function (logits vs the linearized oracle at
    f32), and generate() decodes from them — train 4D, serve with the
    inference path."""
    from dtdl_tpu.models import generate
    from dtdl_tpu.models.transformer import transformer_lm

    cfg = _cfg(n_experts=n_experts, layers_per_stage=2,
               virtual_stages=virtual, moe_dispatch="dense",
               dtype=jnp.float32)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    flax_params = M.to_flax_params(cfg, params)

    model = transformer_lm(
        "tiny", vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, d_ff=cfg.d_ff,
        max_seq=cfg.max_seq, attn_impl="dense", dtype=jnp.float32,
        n_experts=n_experts, moe_every=1)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32)
    # structure check: converted tree == a fresh init's (unboxed) tree
    import flax.linen as nn
    ref_struct = jax.tree_util.tree_structure(
        jax.tree.map(lambda x: 0, nn.unbox(
            model.init(jax.random.PRNGKey(1), toks)["params"])))
    assert jax.tree_util.tree_structure(
        jax.tree.map(lambda x: 0, flax_params)) == ref_struct

    got = model.apply({"params": flax_params}, toks)
    ref, _ = oracle_logits(cfg, params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)

    out = generate(model, flax_params, toks[:, :4], 3)
    assert out.shape == (2, 7)
    assert int(jnp.max(out)) < cfg.vocab_size


def test_factor_mesh():
    # bootstrap regime: every axis >1 as soon as n allows (test meshes)
    assert M.factor_mesh(1) == (1, 1, 1, 1)
    assert M.factor_mesh(2) == (1, 1, 1, 2)
    assert M.factor_mesh(4) == (1, 1, 2, 2)
    assert M.factor_mesh(8) == (1, 2, 2, 2)
    # growth regime: tp within ICI first (cap 8), then pp (cap 4), then dp
    assert M.factor_mesh(16) == (1, 2, 2, 4)
    assert M.factor_mesh(32) == (1, 2, 2, 8)
    assert M.factor_mesh(64) == (1, 2, 4, 8)
    assert M.factor_mesh(128) == (2, 2, 4, 8)
    assert M.factor_mesh(256) == (4, 2, 4, 8)
    # odd factors land on the data axis (it has no divisibility coupling)
    assert M.factor_mesh(6) == (3, 1, 1, 2)
    assert M.factor_mesh(24) == (3, 2, 2, 2)
    for n in (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 64, 128, 256):
        d, s, p, m = M.factor_mesh(n)
        assert d * s * p * m == n
        assert m <= 8 and p <= 4


@pytest.mark.slow
def test_moe_capacity_overflow_drops_and_reports(devices):
    """A starved capacity factor must drop tokens (Switch semantics), report
    an exact dropped fraction, and still train to a finite loss."""
    cfg = _cfg(n_experts=4, capacity_factor=0.25)
    mesh = M.build_4d_mesh(devices)
    opt = optax.sgd(0.05)
    params = M.place_params(mesh, cfg,
                            M.init_params(cfg, jax.random.PRNGKey(7)))
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    batch = M.shard_lm_batch(mesh, _batch(cfg, seed=7))
    _, _, loss, metrics = step(params, opt_state, batch["tokens"],
                               batch["targets"], batch["mask"])
    frac = float(metrics["moe_dropped_frac"])
    # capacity 0.25 leaves room for at most ~1/4 of tokens per expert even
    # under a perfectly uniform router, so a fresh router must drop plenty
    assert 0.05 < frac < 1.0, frac
    assert np.isfinite(float(loss))


def _mesh4(devices, shape):
    from dtdl_tpu.runtime.mesh import build_mesh
    n = int(np.prod(shape))
    return build_mesh(shape=shape, axes=M.AXES, devices=devices[:n])


def _oracle_and_step(cfg, mesh, batch_host, seed=0, lr=0.1):
    """Shared harness: oracle loss+SGD update vs the sharded 4D step."""
    params_host = jax.device_get(M.init_params(cfg, jax.random.PRNGKey(seed)))
    loss_ref, grads_ref = jax.value_and_grad(
        lambda p: oracle_loss(cfg, p, jnp.asarray(batch_host["tokens"]),
                              jnp.asarray(batch_host["targets"]),
                              jnp.asarray(batch_host["mask"])))(params_host)
    params_ref = jax.tree.map(lambda p, g: p - lr * g, params_host, grads_ref)

    opt = optax.sgd(lr)
    params = M.place_params(mesh, cfg, params_host)
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    batch = M.shard_lm_batch(mesh, batch_host)
    params, _, loss, _ = step(params, opt_state, batch["tokens"],
                              batch["targets"], batch["mask"])
    np.testing.assert_allclose(float(loss), float(loss_ref),
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(jax.device_get(params)),
                    jax.tree.leaves(params_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **PARAM_TOL)


@pytest.mark.parametrize("v,n_micro", [(2, 2), (2, 4), (2, 3)])
def test_interleaved_1f1b_matches_oracle(devices, v, n_micro):
    """virtual_stages > 1: chunked ring schedule == the oracle replaying
    the interleaved layer order (incl. a partial last group, M % S != 0)."""
    cfg = _cfg(layers_per_stage=2, virtual_stages=v, n_microbatches=n_micro)
    mesh = M.build_4d_mesh(devices)
    B = 8 if 8 % n_micro == 0 else 2 * n_micro   # global batch % M == 0
    _oracle_and_step(cfg, mesh, _batch(cfg, B=B, S=32, seed=11), seed=12)


def test_interleaved_1f1b_single_stage(devices):
    """S=1, v=2: chunks run sequentially on one device; degenerate ring."""
    cfg = _cfg(n_stages=1, layers_per_stage=2, virtual_stages=2,
               n_microbatches=4)
    mesh = M.build_4d_mesh(devices[:2])   # (1,1,1,2): tp only
    _oracle_and_step(cfg, mesh, _batch(cfg, B=8, S=32, seed=13), seed=14)


@pytest.mark.parametrize("n_micro", [4, 8])
def test_1f1b_four_stages(devices, n_micro):
    """S=4 on a (1,1,4,2) mesh: warmup/cooldown and slot reuse beyond the
    S<=2 cases (round-2 advisor ask)."""
    cfg = _cfg(n_stages=4, n_microbatches=n_micro)
    mesh = _mesh4(devices, (1, 1, 4, 2))
    _oracle_and_step(cfg, mesh, _batch(cfg, B=8, S=32, seed=21), seed=22)


@pytest.mark.slow
def test_1f1b_vocab_indivisible_replicated_head(devices):
    """vocab_size=63 with tp=2: the replicated-head fallback's pmean-based
    grad path must still match the oracle (round-2 advisor ask)."""
    cfg = _cfg(vocab_size=63)
    mesh = M.build_4d_mesh(devices)
    _oracle_and_step(cfg, mesh, _batch(cfg, B=8, S=32, seed=31), seed=32)


def test_4d_checkpoint_resume_equivalence(devices, tmp_path):
    """Sharding-aware snapshot/resume of the 4D path: train 3 steps, save
    the sharded (params, opt_state, step), restore through a FRESH
    Checkpointer against the abstract_state target (fresh-process
    equivalent: only shapes/shardings, no live arrays), train 3 more —
    equivalent to an uninterrupted 6-step run.  (Bitwise on current jax;
    this container's legacy jax 0.4.x re-lowers the step for the restored
    buffer layouts with differently-ordered reductions, so the 3
    post-restore adamw steps drift — tolerance widened per PARAM_TOL's
    cross-version story, not skipped.)"""
    from dtdl_tpu.ckpt import Checkpointer

    cfg = _cfg(n_experts=4)
    mesh = M.build_4d_mesh(devices)
    opt = optax.adamw(1e-3)
    batches = [M.shard_lm_batch(mesh, _batch(cfg, seed=s)) for s in range(6)]

    def run(params, opt_state, steps):
        for b in steps:
            params, opt_state, loss, _ = step(
                params, opt_state, b["tokens"], b["targets"], b["mask"])
        return params, opt_state, loss

    step = M.make_megatron_train_step(cfg, mesh, opt)
    # host-side numpy copy: place_params may alias device buffers, and the
    # donated step would delete p0 out from under the second placement
    p0 = jax.tree.map(np.asarray, M.init_params(cfg, jax.random.PRNGKey(0)))
    params = M.place_params(mesh, cfg, p0)
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    params_ref, _, loss_ref = run(params, opt_state, batches)

    params = M.place_params(mesh, cfg, p0)
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    params, opt_state, _ = run(params, opt_state, batches[:3])
    c1 = Checkpointer(str(tmp_path))
    c1.save(3, {"params": params, "opt_state": opt_state,
                "step": np.asarray(3, np.int64)}, wait=True)
    c1.close()

    c2 = Checkpointer(str(tmp_path))
    a_params, a_opt = M.abstract_state(cfg, mesh, opt)
    like = {"params": a_params, "opt_state": a_opt,
            "step": jax.ShapeDtypeStruct((), np.int64)}
    snap, at = c2.restore(like)
    assert at == 3 and int(snap["step"]) == 3
    # restored leaves land on the mesh with their 4D shardings intact
    some = snap["params"]["blocks"]["wq"]
    assert some.sharding.spec == M.param_specs(cfg)["blocks"]["wq"]
    params2, _, loss2 = run(snap["params"], snap["opt_state"], batches[3:])
    c2.close()

    np.testing.assert_allclose(float(loss2), float(loss_ref),
                               rtol=LOSS_RTOL)
    for a, b in zip(jax.tree.leaves(jax.device_get(params2)),
                    jax.tree.leaves(jax.device_get(params_ref))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **CKPT_PARAM_TOL)


@pytest.mark.slow   # 21s compile — the tier-1 budget-discipline cut
def test_moe_top2_routed_matches_dense(devices):
    """GShard-style top-2: with capacity that can never drop, the routed
    all-to-all dispatch and the dense one-hot dispatch compute the same
    loss and the same parameter update."""
    mesh = M.build_4d_mesh(devices)
    batch_host = _batch(cfg := _cfg(n_experts=4, moe_top_k=2,
                                    moe_dispatch="routed",
                                    capacity_factor=4.0))
    results = []
    for dispatch in ("routed", "dense"):
        c = _cfg(n_experts=4, moe_top_k=2, moe_dispatch=dispatch,
                 capacity_factor=4.0)
        opt = optax.sgd(0.1)
        params = M.place_params(mesh, c, M.init_params(c, jax.random.PRNGKey(0)))
        opt_state = M.init_optimizer(c, mesh, opt, params)
        step = M.make_megatron_train_step(c, mesh, opt)
        b = M.shard_lm_batch(mesh, batch_host)
        params, _, loss, metrics = step(
            params, opt_state, b["tokens"], b["targets"], b["mask"])
        results.append((float(loss), jax.device_get(params), metrics))

    (loss_r, p_r, m_r), (loss_d, p_d, _) = results
    assert float(m_r["moe_dropped_frac"]) == 0.0
    np.testing.assert_allclose(loss_r, loss_d, atol=1e-5, rtol=1e-5)
    for a, b_ in zip(jax.tree.leaves(p_r), jax.tree.leaves(p_d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   **PARAM_TOL)


@pytest.mark.slow   # tier-1 budget-discipline cut (round 22)
def test_moe_aux_loss_flattens_expert_utilization(devices):
    """The Switch load-balance loss is IN the training loss, not just a
    metric: training a routed top-1 MoE at tight capacity (cf=1.0) must
    drive the dropped-assignment fraction down and the aux value toward
    its balanced optimum of 1.0 (uniform f and p give E * sum(f*p) = 1)."""
    cfg = _cfg(n_experts=4, capacity_factor=1.0, moe_aux_weight=0.1)
    mesh = M.build_4d_mesh(devices)
    opt = optax.adam(3e-2)
    params = M.place_params(mesh, cfg,
                            M.init_params(cfg, jax.random.PRNGKey(3)))
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    b = M.shard_lm_batch(mesh, _batch(cfg))
    drops, auxes = [], []
    for _ in range(25):
        params, opt_state, loss, m = step(
            params, opt_state, b["tokens"], b["targets"], b["mask"])
        drops.append(float(m["moe_dropped_frac"]))
        auxes.append(float(m["moe_aux_loss"]))
    assert np.mean(drops[-5:]) < 0.7 * np.mean(drops[:5]), (drops[:5],
                                                            drops[-5:])
    assert np.mean(auxes[-5:]) < np.mean(auxes[:5]), (auxes[:5], auxes[-5:])
    assert np.mean(auxes[-5:]) < 1.1   # near the balanced optimum of 1.0


def test_4d_eval_step_rejects_bad_microbatch_split(devices):
    """An eval batch whose local size does not divide into n_microbatches
    must fail with a ValueError naming the constraint BEFORE shard_map
    tracing turns it into an opaque reshape error."""
    cfg = _cfg(n_microbatches=2)
    mesh = M.build_4d_mesh(devices)
    params = M.place_params(mesh, cfg,
                            M.init_params(cfg, jax.random.PRNGKey(0)))
    eval_step = M.make_megatron_eval_step(cfg, mesh)
    # data axis is 1 on the test mesh: global batch 3 -> b_loc 3, and
    # 3 % n_microbatches(2) != 0
    bad = M.shard_lm_batch(mesh, _batch(cfg, B=3))
    with pytest.raises(ValueError, match="n_microbatches"):
        eval_step(params, bad["tokens"], bad["targets"], bad["mask"])


def test_to_flax_model_mirrors_config():
    """to_flax_model is the single MegatronConfig -> TransformerLM mapping
    (the serving bridge's model half): geometry mirrors the config, the
    bridge-mandated fields are pinned, and overrides win."""
    cfg = _cfg(n_experts=4, moe_top_k=2, capacity_factor=2.0)
    lm = M.to_flax_model(cfg)
    assert (lm.vocab_size, lm.d_model, lm.n_layers, lm.n_heads, lm.d_ff,
            lm.max_seq) == (cfg.vocab_size, cfg.d_model, cfg.n_layers,
                            cfg.n_heads, cfg.d_ff, cfg.max_seq)
    assert lm.head_dim == cfg.head_dim
    # bridge-mandated: megatron puts an MoE in EVERY block, and decode
    # keeps the trained routed-capacity semantics
    assert lm.moe_every == 1
    assert lm.n_experts == 4 and lm.moe_top_k == 2
    assert lm.moe_dispatch == "routed" and lm.capacity_factor == 2.0
    assert lm.attn_impl == "dense" and lm.dtype == jnp.float32
    dense = M.to_flax_model(_cfg())
    assert dense.moe_dispatch == "dense" and dense.n_experts == 0
    # a dense-dispatch-trained MoE keeps dense dispatch at serving time —
    # routing semantics must be the TRAINED ones, not a bridge default
    oracle = M.to_flax_model(_cfg(n_experts=4, moe_dispatch="dense"))
    assert oracle.n_experts == 4 and oracle.moe_dispatch == "dense"
    # overrides win last (e.g. a longer rope table for decode)
    assert M.to_flax_model(cfg, max_seq=4096).max_seq == 4096


@pytest.mark.slow
def test_to_flax_model_roundtrip_trained_params(devices):
    """The serving bridge on TRAINED weights: run real 4D train steps,
    convert with to_flax_model + to_flax_params, and pin logits parity of
    the bridged flax model against the unsharded oracle on the SAME
    trained snapshot — the bridge must hold for the checkpoints serving
    actually loads, not just fresh inits (which sit near the init
    distribution and can mask transposed/mis-mapped kernels)."""
    cfg = _cfg(dtype=jnp.float32)
    mesh = M.build_4d_mesh(devices)
    opt = optax.adam(1e-2)
    params = M.place_params(mesh, cfg, M.init_params(cfg, jax.random.PRNGKey(9)))
    opt_state = M.init_optimizer(cfg, mesh, opt, params)
    step = M.make_megatron_train_step(cfg, mesh, opt)
    for s in range(3):
        batch = M.shard_lm_batch(mesh, _batch(cfg, seed=40 + s))
        params, opt_state, loss, _ = step(
            params, opt_state, batch["tokens"], batch["targets"],
            batch["mask"])
    trained = jax.device_get(params)

    model = M.to_flax_model(cfg)
    flax_params = M.to_flax_params(cfg, trained)
    toks = jnp.asarray(
        np.random.default_rng(41).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32)
    got = model.apply({"params": flax_params}, toks)
    ref, _ = oracle_logits(cfg, trained, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_serve_engine_bridges_4d_training_to_serving(devices):
    """megatron.serve_engine: a 4D-trained snapshot serves through the
    continuous-batching engine ON THE TRAINING MESH, and the batched
    greedy tokens are identical to the bridged flax model's solo
    scalar-cache decode (the train-anywhere/serve-batched contract)."""
    from dtdl_tpu.serve import Request, Scheduler

    cfg = _cfg(dtype=jnp.float32)
    mesh = M.build_4d_mesh(devices)
    params_host = M.init_params(cfg, jax.random.PRNGKey(17))
    engine = M.serve_engine(cfg, params_host, mesh=mesh, n_slots=2,
                            buckets=(8, 16))
    assert engine.model.attn_impl == "dense"   # serving-safe bridge default

    gen = np.random.default_rng(18)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (3, 7, 11)]
    reqs = [Request(p, 4) for p in prompts]
    Scheduler(engine, harvest_lag=2).run(reqs)

    from test_serve import ref_greedy   # tests/ is on sys.path (pytest)

    for req, prompt in zip(reqs, prompts):
        assert req.tokens == ref_greedy(engine.model, engine.params,
                                        prompt, 4)


# ---------------------------------------------------------------------------
# fused-rope attend (round 19): the PR 8 known-remaining
# ---------------------------------------------------------------------------

def test_fused_rope_attend_matches_unfused(devices):
    """On a seq-axis-1 mesh, fuse_rope=True routes the megatron attend
    through flash_attention(rope=..., rope_positions=...) — the rotary
    embedding rides the kernel's tile loads instead of a per-layer
    apply_rope HBM round-trip.  f32 forward parity vs the unfused
    apply_rope + ring path on identical params/batch (the kernel and
    the ring accumulate the same online softmax in f32)."""
    import dataclasses

    cfg = _cfg(n_stages=1, layers_per_stage=2, n_microbatches=2)
    mesh = M.build_4d_mesh(devices[:1])
    batch = _batch(cfg, B=4, S=32, seed=11)
    params_host = jax.device_get(M.init_params(cfg, jax.random.PRNGKey(3)))

    def forward(c):
        params = M.place_params(mesh, c, params_host)
        ev = M.make_megatron_eval_step(c, mesh)
        b = M.shard_lm_batch(mesh, batch)
        out = ev(params, b["tokens"], b["targets"], b["mask"])
        return {k: float(v) for k, v in jax.device_get(out).items()}

    ref = forward(cfg)                                  # auto -> unfused on CPU
    got = forward(dataclasses.replace(cfg, fuse_rope=True))
    assert abs(got["loss"] - ref["loss"]) <= 2e-5, (got, ref)
    assert got["accuracy"] == ref["accuracy"]


def test_ring_fused_rope_matches_unfused_under_sequence_parallelism(devices):
    """fuse_rope=True on a seq>1 mesh (kernel round 2) rides the ring:
    ring_attention(rope=(cos, sin)) rotates each K block inside the
    ppermute schedule at its owner's reconstructed zigzag positions
    instead of materializing a pre-ring apply_rope of K.  The rotation
    arithmetic is elementwise-identical to pre-roping (it commutes with
    the ppermute and with chunk slicing), so the fused forward must be
    f32-EXACT vs the unfused path — this replaces the pre-round-21
    refusal (fuse_rope + seq>1 used to raise by name)."""
    import dataclasses

    cfg = _cfg()
    mesh = M.build_4d_mesh(devices)        # factor_mesh(8): seq axis 2
    if mesh.shape[M.SEQ] < 2:
        pytest.skip("mesh has no sequence parallelism to fuse through")
    batch = _batch(cfg, B=8, S=32, seed=5)
    params_host = jax.device_get(M.init_params(cfg, jax.random.PRNGKey(3)))

    def forward(c):
        params = M.place_params(mesh, c, params_host)
        ev = M.make_megatron_eval_step(c, mesh)
        b = M.shard_lm_batch(mesh, batch)
        out = ev(params, b["tokens"], b["targets"], b["mask"])
        return {k: float(v) for k, v in jax.device_get(out).items()}

    ref = forward(cfg)                     # auto -> unfused on CPU
    got = forward(dataclasses.replace(cfg, fuse_rope=True))
    assert got["loss"] == ref["loss"], (got, ref)
    assert got["accuracy"] == ref["accuracy"]


def test_serve_engine_rules_requires_mesh():
    """rules= without mesh= must fail by name, not silently serve
    unsharded on one chip."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="mesh"):
        M.serve_engine(cfg, params, rules="tp")
