"""The Kimi-Linear blocks of ``TransformerLM`` (Kimi Delta Attention layers,
latent attention without a rotation at two head sizes, a leading dense
layer inside a hybrid stack, a sigmoid router with a scaling factor, a
shared expert without a gate) against the benchmark's plain reference of the
same architecture (``benchmarks/families/kimi_linear.py``, which imports
nothing of the program), on seeded weights at a small size; the share test;
what is missing raises; and that the keywords at their defaults leave the
accepted hybrid step, the dense step and the scalar delta rule as the parent
lowers them.
"""

import hashlib
import pathlib
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
for _p in (str(BENCH), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import modules, tokens, weights                      # noqa: E402

from dtdl_tpu.models import remat_plan                        # noqa: E402
from dtdl_tpu.models.transformer import (HeldExperts, KdaSpec,  # noqa: E402
                                         MlaSpec, TransformerLM,
                                         _SharedExpert)
from dtdl_tpu.ops.gated_delta import gated_delta_rule         # noqa: E402
from dtdl_tpu.parallel.strategy import SingleDevice           # noqa: E402
from dtdl_tpu.train import make_lm_train_step                 # noqa: E402

FAMILY = modules.load_file(str(BENCH / "families" / "kimi_linear.py"),
                           "families")

# every mechanism of the architecture at a size the CPU runs in seconds: the
# model's first five layers (KDA with a dense SwiGLU, KDA, KDA, MLA, KDA
# with experts), 2 KDA heads of 8 under a two-step decay and gate of rank 4,
# 4 latent-attention heads of 8 + 4 (query/key) and 8 (value) from a latent
# of 16, 4 of 16 experts held from id 4 on, 3 a token, a shared expert
CFG = dict(
    model_type="kimi_linear", first_k_dense_replace=1, head_dim=8,
    hidden_size=32, intermediate_size=64, kv_lora_rank=16,
    linear_attn_config=dict(full_attn_layers=[4], head_dim=8,
                            kda_layers=[1, 2, 3, 5], num_heads=2,
                            short_conv_kernel_size=4),
    mla_use_nope=True, model_max_length=256, moe_intermediate_size=24,
    moe_layer_freq=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", num_attention_heads=4,
    num_expert_group=1, num_experts=4, router_num_experts=16,
    first_expert_held=4, num_experts_per_token=3, num_hidden_layers=5,
    num_shared_experts=1, q_lora_rank=None, qk_nope_head_dim=8,
    qk_rope_head_dim=4, rms_norm_eps=1e-5, routed_scaling_factor=2.446,
    tie_word_embeddings=False, topk_group=1, v_head_dim=8, vocab_size=96,
    kda_gate_rank=4, kda_l2norm_eps=1e-6)
ROW = 70        # 69 positions: two chunks of the rule, the second ragged


def _leaf_path(path):
    return "/".join(str(k.key) for k in path if hasattr(k, "key"))


def _layers(cfg, kda, full, dense=1):
    return dict(cfg, num_hidden_layers=len(kda) + len(full),
                first_k_dense_replace=dense, linear_attn_config=dict(
                    cfg["linear_attn_config"], kda_layers=kda,
                    full_attn_layers=full))


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


def _program_loss(model, params, toks):
    logits, muts = model.apply({"params": params}, toks[:, :-1],
                               mutable=["moe_stats", "aux_loss"])
    assert "aux_loss" not in muts
    lse = jax.nn.logsumexp(logits, -1)
    true = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - true), muts


@pytest.mark.parametrize("kda, full, dense", [
    ([1], [], 0), ([], [1], 0), ([1], [], 1), ([1, 2, 3, 5], [4], 1)],
    ids=["kda_with_experts", "mla_with_experts", "kda_with_dense_ffn",
         "the_first_five_layers"])
def test_program_equals_the_plain_reference_on_loss_and_every_gradient(
        kda, full, dense):
    """One layer of each kind, then the cut the cell runs: the loss and every
    leaf's gradient, float32 on both sides."""
    cfg = _layers(CFG, kda, full, dense)
    model, params, made, paths = _model_and_params(cfg)
    toks = jnp.asarray(tokens.batch_tokens(5, 0, 2, ROW, cfg["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        (loss, muts), grads = jax.value_and_grad(
            lambda p: _program_loss(model, p, toks), has_aux=True)(params)
        ref_loss, ref_grads = jax.jit(
            lambda p, t: FAMILY.loss_and_grads(p, t, cfg, "f32"))(made, toks)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    expert_layers = len(kda) + len(full) - dense
    stats = muts.get("moe_stats", {})
    assert len(jax.tree.leaves(stats)) == 3 * expert_layers
    assert all(int(layer["moe"]["overflow_rows"][0]) == 0
               for layer in stats.values())
    grads = dict(zip(paths, jax.tree.leaves(grads)))
    assert set(grads) == set(ref_grads)
    for path in paths:
        want = np.asarray(ref_grads[path])
        gap = np.linalg.norm(np.asarray(grads[path]) - want)
        assert gap <= 5e-4 * max(np.linalg.norm(want), 1e-6), path


def test_the_leading_dense_layer_has_no_router_and_the_rest_no_dense_ffn():
    _, _, made, _ = _model_and_params(CFG)
    first = {p for p in made if p.startswith("block_0/")}
    assert {p.split("/")[1] for p in first} == {"ln_attn", "kda", "ln_mlp",
                                                "mlp"}
    assert made["block_0/mlp/wi/kernel"].shape == (32, 64)
    for i in range(1, 5):
        names = {p.split("/")[1] for p in made if p.startswith(f"block_{i}/")}
        assert names == {"ln_attn", "attn" if i == 3 else "kda", "ln_mlp",
                         "moe"}, i
        assert f"block_{i}/moe/shared/gate/kernel" not in made
        assert made[f"block_{i}/moe/router/kernel"].shape == (32, 16)
    assert {p.split("/")[2] for p in made if p.startswith("block_3/attn/")} \
        == {"q", "kv_a", "kv_norm", "kv_b", "out"}
    assert made["block_3/attn/q/kernel"].shape == (32, 4, 12)
    assert made["block_3/attn/kv_b/kernel"].shape == (16, 4, 16)
    assert made["block_3/attn/out/kernel"].shape == (4, 8, 32)
    assert made["block_1/kda/dt_bias"].shape == (2, 8)
    assert made["block_1/kda/A_log"].shape == (2,)


@pytest.mark.parametrize("act, scale", [("sigmoid", 2.446), ("softmax", 1.0),
                                        ("sigmoid", 1.0)])
def test_the_routers_weights_sum_to_the_scaling_factor(act, scale):
    """The chosen scores are divided by their sum and multiplied by the
    factor, so a token's weights add up to it.  Read off the layer itself:
    with all 16 experts held and every expert given the same weights, the
    routed output is that one expert's output times the sum of the token's
    weights, whichever experts were chosen."""
    d, ff, width, top_k = 16, 12, 16, 3
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (2, 24, d))
    layer = HeldExperts(width, 0, width, top_k, ff, 0, router_act=act,
                        routed_scale=scale, dtype=jnp.float32)
    params = nn.unbox(layer.init(keys[1], x)["params"])
    wi, wg, wo = (jax.random.normal(k, shape) / np.sqrt(shape[0])
                  for k, shape in zip(keys[2:], ((d, ff), (d, ff), (ff, d))))
    params = dict(params, experts={
        name: jnp.broadcast_to(w, (width,) + w.shape)
        for name, w in (("wi", wi), ("wg", wg), ("wo", wo))})
    with jax.default_matmul_precision("highest"):
        out, _ = layer.apply({"params": params}, x, mutable=["moe_stats"])
        one = (jax.nn.silu(x @ wg) * (x @ wi)) @ wo
    assert float(jnp.max(jnp.abs(out - scale * one))) < 1e-5 * float(
        jnp.max(jnp.abs(one)))
    if act == "sigmoid":
        cfg = dict(CFG, num_experts_per_token=top_k,
                   routed_scaling_factor=scale)
        gates, _ = FAMILY._router(
            x[0], {"moe/router/kernel": params["router"]["kernel"]}, cfg)
        assert np.allclose(np.asarray(gates.sum(-1)), scale, rtol=1e-6)


def test_eight_shares_add_up_to_the_uncut_layer():
    """The share test: each of 8 shares of a 16-expert layer holds 2 experts
    and computes its own experts' part (sigmoid router, renormalised, times
    2.446); their sum, with the ungated shared expert counted once, is what
    the plain reference gives for the whole layer (all 16 held)."""
    d, ff, width, held, top_k = 16, 12, 16, 2, 4
    cfg = dict(CFG, hidden_size=d, moe_intermediate_size=ff,
               router_num_experts=width, num_experts=width,
               first_expert_held=0, num_experts_per_token=top_k)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (2, 40, d))

    def layer(first, n_held):
        return HeldExperts(width, first, n_held, top_k, ff, ff,
                           router_act="sigmoid", routed_scale=2.446,
                           shared_gate=False, dtype=jnp.float32)

    params = nn.unbox(layer(0, width).init(keys[1], x)["params"])
    assert "gate" not in params["shared"]
    params = jax.tree.map(
        lambda p: jax.random.normal(keys[2], p.shape) / np.sqrt(p.shape[-2]),
        params)
    flat = {_leaf_path(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}

    def mine(first):
        return dict(params, experts=jax.tree.map(
            lambda w: w[first:first + held], params["experts"]))

    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda row: FAMILY._experts(
            row, {"moe/" + k: v for k, v in flat.items()}, cfg, "f32"))(x)
        shared = _SharedExpert(ff, jnp.float32, gated=False).apply(
            {"params": params["shared"]}, x.reshape(-1, d)).reshape(x.shape)
        total = shared
        for share in range(width // held):
            out, muts = layer(share * held, held).apply(
                {"params": mine(share * held)}, x,
                mutable=["moe_stats", "aux_loss"])
            assert int(muts["moe_stats"]["overflow_rows"][0]) == 0
            assert "aux_loss" not in muts
            total = total + (out - shared)
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_what_is_missing_raises_and_says_so():
    model, params, _, _ = _model_and_params(CFG)
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="trains only"):
        model.apply({"params": params}, toks, decode=True, mutable=["cache"])
    with pytest.raises(ValueError, match="exactly one kind"):
        FAMILY.model_kwargs(_layers(CFG, [1, 2], [2]), True)
    with pytest.raises(ValueError, match="router activation"):
        HeldExperts(4, 0, 2, 1, 8, router_act="tanh").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


def test_the_two_blocks_have_rungs_and_bytes_of_their_own():
    kda, mla = KdaSpec(2, 8, 4, 4), MlaSpec(8, 4, 8, 16)
    assert remat_plan.saved_names(1, linear="kda", held=True) == (
        "kda_loop", "moe_plan")
    assert remat_plan.saved_names(2, linear="kda") == ("kda_loop", "kda_in")
    loop, proj, none = remat_plan.kda_residual_bytes(2, ROW - 1, kda, 2)
    # two chunks of 64 a row: W, U, Q e^Gamma, K e^(..) at 8 and P at 64
    assert (loop, proj, none) == (2 * 128 * 2 * (4 * 8 + 64) * 2,
                                  2 * 69 * 3 * 2 * 8 * 2, 0)
    out, qkv, none = remat_plan.mla_residual_bytes(2, ROW - 1, 32, 4, mla, 2)
    assert out == 2 * 69 * 4 * 8 * 2 + 2 * 4 * 69 * 4
    assert qkv == 2 * 69 * (4 * (2 * 12 + 8) + 32) * 2 and none == 0
    live = remat_plan.hybrid_block_live_bytes
    base = live(2, 69, 32, 2)
    assert live(2, 69, 32, 2, kda=kda) > base
    assert live(2, 69, 32, 2, mla=(4, mla)) > base


# digests of lowered CPU programs as the parent of PR 33 lowers them: the
# rehearsal steps of the accepted hybrid cell and of the dense cell, and the
# scalar delta rule with its gradient on both of its paths.  The keywords of
# PR 33 at their defaults, the flash kernels at equal head sizes and the
# shared loop must leave all four as they were.  The two steps' digests were
# computed anew when flash attention's backward became one kernel.
_PARENT_TEXT = {
    "qwen3next-train-share16":
        "5859cf6d8a6519a6e9bd974dc428bd57fbacbdbeba21b9579aa7fe72e4bb037d",
    "olmo1b-train-b4s2048":
        "89e9235c315c4cf1f5ad1c11758de65e8641877ee209b1db8f9543ecf0b4dfc1",
    "gated_delta_rule.jnp":
        "78ddf8dc7c58edd6f37c6d02bd84a64bac7d3d484e61ed20be375aec692ce9d9",
    "gated_delta_rule.kernel":
        "842f3958ef4b3308ffd2d3fabca0b857ef9cb7fb7ca20acb029d1d8e5031de33",
}


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("cell_name", ["qwen3next-train-share16",
                                       "olmo1b-train-b4s2048"])
def test_the_accepted_cells_rehearsal_steps_lower_as_the_parents(cell_name):
    import run as harness
    from runners import train
    manifest = harness.load_json(str(BENCH.parent / "BENCHMARK.json"))
    cell, cfg = harness.resolve(manifest, cell_name, True)
    plan = train.make_plan(cell, cfg)
    state = jax.eval_shape(plan.build, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cell["batch_per_chip"], cell["row_tokens"]), jnp.int32)}
    text = make_lm_train_step(SingleDevice(), vocab_chunk_size=0).lower(
        state, batch).as_text()
    assert _digest(text) == _PARENT_TEXT[cell_name]


@pytest.mark.parametrize("path, key_heads, heads, dim, length", [
    ("jnp", 2, 4, 16, 150), ("kernel", 1, 2, 128, 200)])
def test_the_scalar_delta_rule_lowers_as_the_parents(path, key_heads, heads,
                                                     dim, length):
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    args = (sds(2, length, key_heads, dim), sds(2, length, key_heads, dim),
            sds(2, length, heads, dim), sds(2, length, heads),
            sds(2, length, heads))
    text = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(gated_delta_rule(*a, operand_dtype=jnp.bfloat16)),
        argnums=(0, 1, 2, 3, 4))).lower(*args).as_text()
    assert _digest(text) == _PARENT_TEXT["gated_delta_rule." + path]
