"""The JoyAI-LLM-Flash blocks of ``TransformerLM`` (latent attention with a
query latent and a rotated shared key part on every layer, the rotation by
adjacent pairs, a multi-token-prediction module and its second loss, a
sigmoid router over 16 experts with 4 held and a shared expert) against the
benchmark's plain reference of the same architecture
(``benchmarks/families/joyai_llm_flash.py``, which imports nothing of the
program), on seeded weights at a small size; the share test; the module's
causality; the plan's blocks; and that the keywords at their defaults leave
every accepted cell's rehearsal step as the parent lowers it.
"""

import functools
import hashlib
import pathlib
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
for _p in (str(BENCH), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import modules, tokens, weights                      # noqa: E402

from dtdl_tpu.models import remat_plan, transformer           # noqa: E402
from dtdl_tpu.models.transformer import (HeldExperts,         # noqa: E402
                                         LatentAttention, MlaSpec,
                                         MtpLogits, TransformerLM,
                                         _SharedExpert)
from dtdl_tpu.ops.rope import apply_rope, rope_frequencies    # noqa: E402
from dtdl_tpu.parallel.strategy import SingleDevice           # noqa: E402
from dtdl_tpu.train import make_lm_train_step                 # noqa: E402
from dtdl_tpu.train.state import TrainState                   # noqa: E402

FAMILY = modules.load_file(str(BENCH / "families" / "joyai_llm_flash.py"),
                           "families")

# every mechanism of the architecture at a size the CPU runs in seconds: a
# leading dense layer and four expert layers, each with latent attention of
# 4 heads of 8 + 4 (query/key) and 8 (value) from a query latent of 12 and a
# key/value latent of 16 + 4, the 4-wide parts rotated by adjacent pairs;
# 4 of 16 experts held from id 4 on, 3 a token, a shared expert; one
# multi-token-prediction module at a weight of 0.3
CFG = dict(
    model_type="joyai_llm_flash", attention_bias=False, ep_size=1,
    first_k_dense_replace=1, head_dim=8, hidden_act="silu", hidden_size=32,
    intermediate_size=64, kv_lora_rank=16, max_position_embeddings=256,
    moe_intermediate_size=24, moe_layer_freq=1, n_group=1,
    n_routed_experts=4, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=4, num_experts_per_tok=3, num_hidden_layers=5,
    num_key_value_heads=4, num_nextn_predict_layers=1, q_lora_rank=12,
    qk_head_dim=12, qk_nope_head_dim=8, qk_rope_head_dim=4,
    rms_norm_eps=1e-6, rope_interleave=True, rope_scaling=None,
    rope_theta=10000, routed_scaling_factor=2.5, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
    v_head_dim=8, vocab_size=96, router_num_experts=16, first_expert_held=4,
    mtp_loss_weight=0.3)
ROW = 40        # 39 inputs, 38 positions of the module


def _leaf_path(path):
    return "/".join(str(k.key) for k in path if hasattr(k, "key"))


def _model_and_params(cfg, seed=7, dtype=jnp.float32, **over):
    kwargs = dict(FAMILY.model_kwargs(cfg, True), **over)
    model = TransformerLM(dtype=dtype, **kwargs)
    abstract = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, ROW - 1), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [_leaf_path(p) for p, _ in flat]
    shapes = {p: tuple(leaf.shape) for p, (_, leaf) in zip(paths, flat)}
    made = weights.make_params(weights.seed_key(seed), shapes,
                               FAMILY.leaf_moments)
    params = jax.tree_util.tree_unflatten(treedef, [made[p] for p in paths])
    return model, params, made, paths


def _cross_entropy(logits, targets):
    lse = jax.nn.logsumexp(logits, -1)
    true = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(lse - true)


def _program_loss(model, params, toks, weight):
    out, muts = model.apply({"params": params}, toks[:, :-1],
                            mutable=["moe_stats", "aux_loss"])
    assert "aux_loss" not in muts
    if not isinstance(out, tuple):
        return _cross_entropy(out, toks[:, 1:]), muts
    logits, mtp = out
    assert isinstance(mtp, MtpLogits) and mtp.weight == weight
    assert mtp.logits.shape == (toks.shape[0], toks.shape[1] - 2,
                                logits.shape[-1])
    return (_cross_entropy(logits, toks[:, 1:])
            + weight * _cross_entropy(mtp.logits, toks[:, 2:])), muts


def _compare(cfg, seed=7, **over):
    """The program's loss and every gradient against the reference's, both
    float32; returns the worst leaf's gap (its norm over the reference's)
    and the two losses."""
    model, params, made, paths = _model_and_params(cfg, seed, **over)
    toks = jnp.asarray(tokens.batch_tokens(5, 0, 2, ROW, cfg["vocab_size"]))
    weight = cfg["mtp_loss_weight"] if cfg["num_nextn_predict_layers"] \
        else None
    with jax.default_matmul_precision("highest"):
        (loss, muts), grads = jax.jit(jax.value_and_grad(
            lambda p: _program_loss(model, p, toks, weight),
            has_aux=True))(params)
        ref_loss, ref_grads = jax.jit(
            lambda p, t: FAMILY.loss_and_grads(p, t, cfg, "f32"))(made, toks)
    stats = muts.get("moe_stats", {})
    assert all(int(layer["moe"]["overflow_rows"][0]) == 0
               for layer in jax.tree.leaves(
                   stats, is_leaf=lambda x: isinstance(x, dict)
                   and "moe" in x))
    grads = dict(zip(paths, jax.tree.leaves(grads)))
    assert set(grads) == set(ref_grads)
    worst = max(
        np.linalg.norm(np.asarray(grads[p]) - np.asarray(ref_grads[p]))
        / max(np.linalg.norm(np.asarray(ref_grads[p])), 1e-6) for p in paths)
    return worst, float(loss), float(ref_loss)


@pytest.mark.parametrize("layers, dense, mtp", [
    (1, 1, 0), (2, 1, 1), (5, 1, 1)],
    ids=["dense_layer", "two_layers_and_the_module",
         "the_cut_the_cell_runs"])
def test_program_equals_the_plain_reference_on_loss_and_every_gradient(
        layers, dense, mtp):
    """One latent layer with experts, one with the dense FFN, then with the
    multi-token-prediction module and its second loss, then the cut the
    cell runs: the loss and every leaf's gradient, float32 on both sides."""
    cfg = dict(CFG, num_hidden_layers=layers, first_k_dense_replace=dense,
               num_nextn_predict_layers=mtp)
    worst, loss, ref_loss = _compare(cfg)
    assert loss == pytest.approx(ref_loss, rel=1e-5)
    assert worst <= 5e-4


def test_the_rotation_turns_adjacent_pairs():
    """``apply_rope(interleave=True)`` against the reference's rotation by
    strided slices, and by hand at one position: frequency ``i`` turns the
    pair ``(2i, 2i + 1)`` by ``p * theta ** (-2i / r)``.  The half-split
    rotation of the same vector is another vector."""
    r, s = 8, 5
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 1, s, r))
    cos, sin = rope_frequencies(r, s, 10000.0)
    pairs = apply_rope(x, cos, sin, interleave=True)
    want = FAMILY._rotated(x[0, 0], dict(rope_theta=10000.0))
    assert np.allclose(np.asarray(pairs[0, 0]), np.asarray(want), atol=1e-6)
    p, i = 3, 2
    angle = p * 10000.0 ** (-2 * i / r)
    a, b = float(x[0, 0, p, 2 * i]), float(x[0, 0, p, 2 * i + 1])
    assert float(pairs[0, 0, p, 2 * i]) == pytest.approx(
        a * np.cos(angle) - b * np.sin(angle), abs=1e-5)
    assert float(pairs[0, 0, p, 2 * i + 1]) == pytest.approx(
        a * np.sin(angle) + b * np.cos(angle), abs=1e-5)
    halves = apply_rope(x, cos, sin)
    assert not np.allclose(np.asarray(halves), np.asarray(pairs), atol=1e-2)


def test_a_half_split_rotation_of_the_same_weights_fails_the_check(
        monkeypatch):
    """The program with the rotation by halves (``apply_rope`` without
    ``interleave`` put in the layer's place here), against the reference by
    adjacent pairs on the same weights: the gradients leave the tolerance
    the right layout holds (the comparison above) a hundredfold, the loss
    tenfold (the rotated part is 4 of a head's 12 query/key channels)."""
    monkeypatch.setattr(transformer, "apply_rope",
                        lambda t, cos, sin, interleave: apply_rope(t, cos,
                                                                   sin))
    cfg = dict(CFG, num_hidden_layers=2)
    worst, loss, ref_loss = _compare(cfg, attn_impl="dense")
    assert worst > 100 * 5e-4
    assert abs(loss - ref_loss) > 10 * 1e-5 * abs(ref_loss)


def test_the_module_sees_no_token_past_the_one_it_is_given():
    """Changing the input ``t_j`` leaves the module's logits at every
    position below ``j - 1`` as they were and changes them at ``j - 1``
    (which reads ``Emb t_j``) and after."""
    cfg = dict(CFG, num_hidden_layers=2)
    model, params, _, _ = _model_and_params(cfg, attn_impl="dense")
    toks = jnp.asarray(tokens.batch_tokens(9, 0, 1, ROW,
                                           cfg["vocab_size"]))[:, :-1]
    j = 20
    other = toks.at[0, j].set((toks[0, j] + 1) % cfg["vocab_size"])

    @jax.jit
    def ahead(t):
        (_, mtp), _ = model.apply({"params": params}, t,
                                  mutable=["moe_stats"])
        return mtp.logits[0]

    with jax.default_matmul_precision("highest"):
        before, after = np.asarray(ahead(toks)), np.asarray(ahead(other))
    assert np.array_equal(before[:j - 1], after[:j - 1])
    moved = np.abs(before - after).max(axis=-1)
    assert moved[j - 1] > 1e-4 and (moved[j - 1:] > 0).all()


def test_four_shares_add_up_to_the_uncut_layer():
    """The share test: each of 4 shares of a 16-expert layer holds 4
    experts and computes its own experts' part (sigmoid router,
    renormalised, times 2.5); their sum, with the ungated shared expert
    counted once, is what the plain reference gives for the whole layer
    (all 16 held)."""
    d, ff, width, held, top_k = 16, 12, 16, 4, 8
    cfg = dict(CFG, hidden_size=d, moe_intermediate_size=ff,
               router_num_experts=width, n_routed_experts=width,
               first_expert_held=0, num_experts_per_tok=top_k)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (2, 40, d))

    def layer(first, n_held):
        return HeldExperts(width, first, n_held, top_k, ff, ff,
                           router_act="sigmoid", routed_scale=2.5,
                           shared_gate=False, dtype=jnp.float32)

    params = nn.unbox(layer(0, width).init(keys[1], x)["params"])
    params = jax.tree.map(
        lambda p: jax.random.normal(keys[2], p.shape) / np.sqrt(p.shape[-2]),
        params)
    flat = {_leaf_path(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}

    def mine(first):
        return dict(params, experts=jax.tree.map(
            lambda w: w[first:first + held], params["experts"]))

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda row: FAMILY._experts(
            row, {"moe/" + k: v for k, v in flat.items()}, cfg, "f32")))(x)
        shared = _SharedExpert(ff, jnp.float32, gated=False).apply(
            {"params": params["shared"]}, x.reshape(-1, d)).reshape(x.shape)
        total = shared
        for share in range(width // held):
            out, muts = jax.jit(functools.partial(
                layer(share * held, held).apply,
                mutable=["moe_stats", "aux_loss"]))(
                {"params": mine(share * held)}, x)
            assert int(muts["moe_stats"]["overflow_rows"][0]) == 0
            total = total + (out - shared)
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_without_a_query_latent_or_a_rotation_the_layer_is_kimis():
    """``mla_q_rank`` 0 and no rotation: latent attention has the
    Kimi-Linear layer's parameter paths (``q`` in one projection); with a
    query latent ``q_a``, its norm and ``q_b`` take its place."""
    x = jnp.zeros((1, 6, 32))

    def paths(**kw):
        layer = LatentAttention(4, 8, 4, 8, 16, dtype=jnp.float32, **kw)
        cos, sin = rope_frequencies(4, 6)
        params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x, cos,
                                sin)["params"]
        return {_leaf_path(p) for p, _ in
                jax.tree_util.tree_flatten_with_path(params)[0]}

    kimi = {"q/kernel", "kv_a/kernel", "kv_norm/scale", "kv_b/kernel",
            "out/kernel"}
    assert paths() == kimi
    assert paths(q_rank=12, rope="pairs") == kimi - {
        "q/kernel"} | {"q_a/kernel", "q_a_norm/scale", "q_b/kernel"}


def test_the_step_adds_the_modules_loss_at_its_weight():
    """Through ``make_lm_train_step``: the loss is the next-token mean plus
    0.3 times the module's mean over the targets two ahead, and the step
    reports the module's as ``mtp_loss``; a model without the module has
    no such metric."""
    cfg = dict(CFG, num_hidden_layers=2)
    model, params, _, _ = _model_and_params(cfg, attn_impl="dense")
    toks = jnp.asarray(tokens.batch_tokens(5, 0, 2, ROW, cfg["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        (logits, mtp), _ = jax.jit(lambda p, t: model.apply(
            {"params": p}, t, mutable=["moe_stats"]))(params, toks[:, :-1])
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=optax.adamw(3e-4))
        _, metrics = make_lm_train_step(SingleDevice())(state,
                                                        {"tokens": toks})
    main = float(_cross_entropy(logits, toks[:, 1:]))
    ahead = float(_cross_entropy(mtp.logits, toks[:, 2:]))
    assert float(metrics["mtp_loss"]) == pytest.approx(ahead, rel=1e-5)
    assert float(metrics["loss"]) == pytest.approx(main + 0.3 * ahead,
                                                   rel=1e-5)
    plain, plain_params, _, _ = _model_and_params(
        dict(cfg, num_nextn_predict_layers=0), attn_impl="dense")
    _, metrics = make_lm_train_step(SingleDevice())(
        TrainState.create(apply_fn=plain.apply, params=plain_params,
                          tx=optax.adamw(3e-4)), {"tokens": toks})
    assert "mtp_loss" not in metrics


def test_what_is_missing_raises_and_says_so():
    model, params, _, _ = _model_and_params(dict(CFG, num_hidden_layers=2))
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="trains only"):
        model.apply({"params": params}, toks, decode=True, mutable=["cache"])
    with pytest.raises(NotImplementedError, match="chunked loss"):
        model.apply({"params": params}, toks, return_hidden=True,
                    mutable=["moe_stats"])
    with pytest.raises(NotImplementedError, match="modules"):
        model.clone(mtp_layers=2).init(jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="sigmoid"):
        FAMILY.model_kwargs(dict(CFG, scoring_func="softmax"), True)


def test_the_plan_holds_the_modules_block_and_its_head():
    """With a limit, the plan has a rung for the module's block after the
    model's, charges the module's parameters as that block's gradient, and
    counts two heads' logits at the head's instant; the module's block is
    an expert block with latent attention."""
    cfg = dict(CFG, num_hidden_layers=2)
    model, params, _, _ = _model_and_params(cfg)
    toks = jnp.zeros((2, ROW - 1), jnp.int32)

    def plan_of(m, limit):
        with remat_plan.step_memory("lm_train_step", 0, limit):
            jax.eval_shape(lambda p: m.apply({"params": p}, toks,
                                             mutable=["moe_stats"]), params)
        from dtdl_tpu.runtime import compile_cache
        return compile_cache.remat_plans()[-1]

    plan = plan_of(model, 10**9)
    assert len(plan.rungs) == 3 and min(plan.rungs) >= 1
    t, v = 2 * (ROW - 1), cfg["vocab_size"]
    held = remat_plan.model_held_bytes(2, ROW - 1, 32, 0, 3, v, 0, 4,
                                       block_live_bytes=0, heads=2)
    assert held.start - remat_plan.model_held_bytes(
        2, ROW - 1, 32, 0, 3, v, 0, 4, block_live_bytes=0).start \
        == t * v * (4 + 4)
    sizes = MlaSpec(8, 4, 8, 16, 12, "pairs")
    live = remat_plan.hybrid_block_live_bytes
    assert live(2, 39, 32, 4, mla=(4, sizes)) - live(
        2, 39, 32, 4, mla=(4, sizes._replace(q_rank=0, rope="none"))) \
        == 2 * 78 * (2 * 12 + 4 * 12) * 4


# digests of lowered CPU programs as the commit before these keywords
# lowers them: the rehearsal steps of the two accepted cells no other file
# pins, the four-chip one under its own strategy (a data-parallel mesh of
# four CPU devices), without a limit and at a v5e's.  The other five are
# pinned to the same parent's text in tests/test_afmoe.py (four of them at
# both limits, Trinity at three) and tests/test_kimi_linear.py.  The new
# keywords at their defaults (no query latent, no rotation in latent
# attention, no multi-token-prediction module) must leave all seven as they
# were.  (The zipf cell's program is the uniform one's.)  All were computed
# anew when flash attention's backward became one kernel.
_V5E = 16_844_324_864
_PARENT_TEXT = {
    ("olmo1b-train-ddp4", None):
        "a1a6eed4b4781c340c0a1eccaf5b9baa23208cc0be743db4beccae35d0565a36",
    ("olmo1b-train-ddp4", _V5E):
        "c7ee64bd9064b2362b09e65abffc62397ea10040b4e3835c0063949896ff7d10",
    ("olmo1b-train-b4s2048-zipf", None):
        "89e9235c315c4cf1f5ad1c11758de65e8641877ee209b1db8f9543ecf0b4dfc1",
    ("olmo1b-train-b4s2048-zipf", _V5E):
        "5c1e7f9667f1582867d25949d1831a7ab25911670a5563fac049557e78032a95",
}


@pytest.mark.parametrize("cell_name, limit", list(_PARENT_TEXT),
                         ids=[f"{c}-{'v5e_limit' if lim else 'no_limit'}"
                              for c, lim in _PARENT_TEXT])
def test_the_accepted_cells_rehearsal_steps_lower_as_the_parents(
        cell_name, limit, devices, monkeypatch):
    import run as harness
    from runners import train

    from dtdl_tpu.parallel import DataParallel
    from dtdl_tpu.runtime.mesh import DATA_AXIS
    monkeypatch.setattr(remat_plan, "device_bytes_limit", lambda: limit)
    manifest = harness.load_json(str(BENCH.parent / "BENCHMARK.json"))
    cell, cfg = harness.resolve(manifest, cell_name, True)
    plan = train.make_plan(cell, cfg)
    state = jax.eval_shape(plan.build, jax.random.PRNGKey(0))
    chips = cell["chips"]
    strategy = SingleDevice() if chips == 1 else DataParallel(
        mesh=jax.sharding.Mesh(np.asarray(devices[:chips]), (DATA_AXIS,)))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cell["batch_per_chip"] * chips, cell["row_tokens"]), jnp.int32)}
    text = make_lm_train_step(strategy, vocab_chunk_size=0).lower(
        state, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT_TEXT[cell_name, limit]


# ---------------------------------------------------------------------------
# the cell in the benchmark: the module's anchor in the compiled step, and
# the cell's rehearsal as the benchmark's command runs it
# ---------------------------------------------------------------------------

CELL = "joyaiflash-train-share32"


def _harness():
    import run as harness
    manifest = harness.load_json(str(BENCH.parent / "BENCHMARK.json"))
    return harness, manifest


def test_the_mtp_anchor_matches_the_modules_instructions_alone():
    """``mtp.ms`` reads the events whose result carries a dimension only the
    module's arrays have (``metrics/mtp.ms.py``).  In the rehearsal's step
    compiled here, every instruction it matches that carries an
    ``op_name`` lies under the module's scope ``mtp``, and there are such
    instructions in the forward, the recomputation and the backward pass.
    (Instructions without an ``op_name`` are the interpreter's loops of the
    flash kernels, which the chip runs as Mosaic calls of padded shapes.)"""
    import re

    from runners import train

    from dtdl_tpu.obs.trace import device_component
    harness, manifest = _harness()
    cell, cfg = harness.resolve(manifest, CELL, True)
    reader = harness.load_module("metrics", "mtp.ms")
    plan = train.make_plan(cell, cfg)
    state = jax.eval_shape(plan.build, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cell["batch_per_chip"], cell["row_tokens"]), jnp.int32)}
    text = make_lm_train_step(SingleDevice()).lower(
        state, batch).compile().as_text()
    dims = reader.mtp_dims(cfg, cell)
    assert dims == {148, 296, 888}
    computation, passes, outside = None, set(), []
    for line in text.splitlines():
        if not line.startswith(" "):
            computation = line.split()[1 if line.startswith("ENTRY") else 0] \
                if line.strip() else computation
            continue
        op = re.sub(r"^\s*(ROOT )?", "", line)
        if computation.lstrip("%").startswith("fused") \
                or not reader.is_mtp_event(op, dims):
            continue
        name = re.search(r'op_name="([^"]*)"', op)
        if name is None:
            continue
        if "mtp" in re.split(r"[/()]", name.group(1)):
            passes.add(device_component(name.group(1))[1])
        else:
            outside.append(op[:160])
    assert not outside, outside[:5]
    assert passes == {"forward", "recompute", "backward"}


def test_cell_rehearses_through_run_py():
    """One traced rehearsal (the untraced line's shape is the harness's own
    and is checked on the accepted cells)."""
    import json
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 99), "--seconds", "0.5", "--trace", "1",
         "--rehearse"],
        cwd=str(BENCH.parent), env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3 and line["rehearsal"] is True
    assert line["compared"]["nonfinite_losses"]["value"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "step.host_dispatch_ms" in line["metrics"]
    assert "mtp.ms" not in line["metrics"]              # no device line
