"""The Trinity (``afmoe``) blocks of ``TransformerLM`` (sliding-window and
full attention in one stack, a rotation on the windowed layers alone, gated
grouped-query heads with q/k norm and a gate projection of their own, four
norms a block, a scaled embedding, a leading dense layer, a sigmoid router
over held experts) against the benchmark's plain reference of the same
architecture (``benchmarks/families/afmoe.py``, which imports nothing of
the program), on seeded weights at a small size; the share test; what is
missing raises; and that the new keywords at their defaults leave the
accepted cells' steps as the parent lowers them.
"""

import functools
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

from dtdl_tpu.models.transformer import (HeldExperts,         # noqa: E402
                                         TransformerLM, _SharedExpert)
from dtdl_tpu.parallel.strategy import SingleDevice           # noqa: E402
from dtdl_tpu.train import make_lm_train_step                 # noqa: E402

FAMILY = modules.load_file(str(BENCH / "families" / "afmoe.py"), "families")
WINDOWED, FULL = "sliding_attention", "full_attention"

# every mechanism of the architecture at a size the CPU runs in seconds: 4
# query heads of 8 over 2 key/value heads, a window of 24 in rows of 69
# positions (so the band leaves out most of a windowed layer's triangle), a
# dense SwiGLU in the leading layer, 4 of 16 experts held from id 4 on, 3 a
# token, a shared expert
CFG = dict(
    model_type="afmoe", head_dim=8, hidden_size=32, intermediate_size=64,
    layer_types=[WINDOWED, WINDOWED, FULL, WINDOWED, WINDOWED],
    max_position_embeddings=256, moe_intermediate_size=24, mup_enabled=True,
    n_group=1, num_attention_heads=4, num_dense_layers=1,
    num_expert_groups=1, num_experts=4, router_num_experts=16,
    first_expert_held=4, num_experts_per_tok=3, num_hidden_layers=5,
    num_key_value_heads=2, num_limited_groups=1, num_shared_experts=1,
    rms_norm_eps=1e-5, rope_scaling=None, rope_theta=10000, route_norm=True,
    route_scale=2.826, score_func="sigmoid", sliding_window=24,
    tie_word_embeddings=False, topk_group=1, vocab_size=96)
ROW = 70


def _leaf_path(path):
    return "/".join(str(k.key) for k in path if hasattr(k, "key"))


def _layers(cfg, types, dense=1):
    return dict(cfg, num_hidden_layers=len(types), num_dense_layers=dense,
                layer_types=list(types))


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


TOKENS = jnp.asarray(tokens.batch_tokens(5, 0, 2, ROW, CFG["vocab_size"]))


def _program(cfg, **over):
    """``(loss, grads by path, stats)`` of the program built with ``over``
    on top of the family's keywords, on seed 7's weights."""
    model, params, _, paths = _model_and_params(cfg, **over)
    with jax.default_matmul_precision("highest"):
        (loss, muts), grads = jax.jit(jax.value_and_grad(
            lambda p: _program_loss(model, p, TOKENS), has_aux=True))(params)
    return (float(loss), dict(zip(paths, jax.tree.leaves(grads))),
            muts.get("moe_stats", {}))


@functools.cache
def _reference(types, dense):
    """``(loss, grads by path)`` of the plain reference on the same."""
    cfg = _layers(CFG, types, dense)
    _, _, made, _ = _model_and_params(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(lambda p, t: FAMILY.loss_and_grads(
            p, t, cfg, "f32"))(made, TOKENS)
    return float(loss), grads


def _worst_gap(grads, ref_grads):
    return max(
        (np.linalg.norm(np.asarray(grads[p]) - np.asarray(ref_grads[p]))
         / max(np.linalg.norm(np.asarray(ref_grads[p])), 1e-6), p)
        for p in ref_grads if p in grads)


MIXED = (WINDOWED, FULL, WINDOWED)


@pytest.mark.parametrize("types, dense", [
    ((WINDOWED,), 0), (MIXED, 1),
    ((WINDOWED, WINDOWED, FULL, WINDOWED, WINDOWED), 1)],
    ids=["windowed_with_experts", "dense_then_full_and_windowed_experts",
         "the_five_layers"])
def test_program_equals_the_plain_reference_on_loss_and_every_gradient(
        types, dense):
    """A windowed expert layer alone, a stack with a dense layer, a full and
    a windowed expert layer, then the cut the cell runs: the loss and every
    leaf's gradient, float32 on both sides."""
    loss, grads, stats = _program(_layers(CFG, types, dense))
    ref_loss, ref_grads = _reference(types, dense)
    assert loss == pytest.approx(ref_loss, rel=1e-5)
    assert set(grads) == set(ref_grads)
    assert len(jax.tree.leaves(stats)) == 3 * (len(types) - dense)
    assert all(int(layer["moe"]["overflow_rows"][0]) == 0
               for layer in stats.values())
    gap, path = _worst_gap(grads, ref_grads)
    assert gap <= 5e-4, path


@pytest.mark.parametrize("what, over", [
    ("the full layer is rotated", dict(layer_rotates=(True, True, True))),
    ("a windowed layer is not rotated",
     dict(layer_rotates=(True, False, False))),
    ("no layer is windowed", dict(layer_windows=(0, 0, 0))),
    ("the full layer is windowed", dict(layer_windows=(24, 24, 24))),
    ("the embedding is not scaled", dict(embed_scale=1.0)),
    ("there is no gate", dict(attn_gate=False)),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else "")
def test_a_layer_of_the_wrong_kind_is_told_from_the_reference(what, over):
    """The comparison above is tight enough to see each mechanism: with one
    of them changed in the program (the reference left alone) the loss or a
    gradient is far outside its tolerance."""
    loss, grads, _ = _program(_layers(CFG, MIXED, 1), **over)
    ref_loss, ref_grads = _reference(MIXED, 1)
    assert (_worst_gap(grads, ref_grads)[0] > 1e-2
            or abs(loss - ref_loss) > 1e-3 * ref_loss), what


@pytest.mark.parametrize("norm, scaled", [
    ("ln_attn_out", ("attn/out/kernel",)),
    ("ln_mlp_out", ("mlp/wo/kernel", "moe/experts/wo",
                    "moe/shared/wo/kernel"))])
def test_either_output_norm_left_out_would_show(norm, scaled):
    """A norm on a sublayer's output makes the block blind to the scale of
    that sublayer's last projection (to ``eps``): with every such projection
    tripled the loss stands where it stood.  Without the norm (the same
    model with ``post_norms`` off, the norms' weights dropped) it moves, as
    it does against the reference, which has both."""
    cfg = _layers(CFG, MIXED, 1)
    model, params, made, paths = _model_and_params(cfg)

    def tripled(tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        return jax.tree_util.tree_unflatten(treedef, [
            3.0 * leaf if _leaf_path(path).partition("/")[2] in scaled
            else leaf for path, leaf in flat])

    with jax.default_matmul_precision("highest"):
        loss = float(_program_loss(model, params, TOKENS)[0])
        same = float(_program_loss(model, tripled(params), TOKENS)[0])
        bare = TransformerLM(dtype=jnp.float32, **dict(
            FAMILY.model_kwargs(cfg, True), post_norms=False))
        stripped = {k: {n: v for n, v in block.items()
                        if not n.endswith("_out")}
                    if k.startswith("block_") else block
                    for k, block in params.items()}
        without = float(_program_loss(bare, stripped, TOKENS)[0])
        moved = float(_program_loss(bare, tripled(stripped), TOKENS)[0])
    assert loss == pytest.approx(_reference(MIXED, 1)[0], rel=1e-5)
    assert same == pytest.approx(loss, rel=1e-4)
    assert abs(without - loss) > 1e-3 * loss
    assert abs(moved - without) > 1e-3 * without
    assert {p.split("/")[1] for p in paths if p.startswith("block_1/")} == {
        "ln_attn", "attn", "ln_attn_out", "ln_mlp", "moe", "ln_mlp_out"}


def test_the_blocks_hold_what_the_configuration_says():
    _, _, made, _ = _model_and_params(CFG)
    assert {p.split("/")[1] for p in made if p.startswith("block_0/")} == {
        "ln_attn", "attn", "ln_attn_out", "ln_mlp", "mlp", "ln_mlp_out"}
    assert made["block_0/mlp/wi/kernel"].shape == (32, 64)
    for i in range(5):
        assert {p.split("/")[2] for p in made
                if p.startswith(f"block_{i}/attn/")} == {
            "q", "k", "v", "gate_proj", "q_norm", "k_norm", "out"}
        assert made[f"block_{i}/attn/q/kernel"].shape == (32, 4, 8)
        assert made[f"block_{i}/attn/gate_proj/kernel"].shape == (32, 4, 8)
        assert made[f"block_{i}/attn/k/kernel"].shape == (32, 2, 8)
        assert made[f"block_{i}/attn/q_norm/scale"].shape == (8,)
        assert made[f"block_{i}/attn/out/kernel"].shape == (4, 8, 32)
    for i in range(1, 5):
        assert made[f"block_{i}/moe/router/kernel"].shape == (32, 16)
        assert made[f"block_{i}/moe/experts/wi"].shape == (4, 32, 24)
        assert f"block_{i}/moe/shared/gate/kernel" not in made
    assert made["head"].shape == made["embed"].shape == (96, 32)
    kwargs = FAMILY.model_kwargs(CFG, True)
    assert kwargs["layer_windows"] == (24, 24, 0, 24, 24)
    assert kwargs["layer_rotates"] == (True, True, False, True, True)
    assert kwargs["embed_scale"] == pytest.approx(32 ** 0.5)


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The share test: each of 16 shares of a 128-expert layer holds 8
    experts and computes its own experts' part (sigmoid router over all 128,
    the 8 largest renormalised, times 2.826); their sum, with the ungated
    shared expert counted once, is what the plain reference gives for the
    whole layer (all 128 held)."""
    d, ff, width, held, top_k = 16, 12, 128, 8, 8
    cfg = dict(CFG, hidden_size=d, moe_intermediate_size=ff,
               router_num_experts=width, num_experts=width,
               first_expert_held=0, num_experts_per_tok=top_k)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (2, 40, d))

    def layer(first, n_held):
        return HeldExperts(width, first, n_held, top_k, ff, ff,
                           router_act="sigmoid", routed_scale=2.826,
                           shared_gate=False, dtype=jnp.float32)

    params = nn.unbox(layer(0, width).init(keys[1], x)["params"])
    assert "gate" not in params["shared"]
    params = jax.tree.map(
        lambda p: jax.random.normal(keys[2], p.shape) / np.sqrt(p.shape[-2]),
        params)
    flat = {"moe/" + _leaf_path(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    held_all = tuple(flat[f"moe/experts/{n}"] for n in ("wi", "wg", "wo"))

    def mine(first):
        return dict(params, experts=jax.tree.map(
            lambda w: w[first:first + held], params["experts"]))

    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda row: FAMILY.routed(
            row, flat, cfg, "f32", 0, held_all)
            + FAMILY.shared(row, flat, "f32"))(x)
        shared = _SharedExpert(ff, jnp.float32, gated=False).apply(
            {"params": params["shared"]}, x.reshape(-1, d)).reshape(x.shape)
        total = shared
        for share in range(width // held):
            out, muts = jax.jit(functools.partial(
                layer(share * held, held).apply,
                mutable=["moe_stats", "aux_loss"]))(
                {"params": mine(share * held)}, x)
            assert int(muts["moe_stats"]["overflow_rows"][0]) == 0
            assert "aux_loss" not in muts
            total = total + (out - shared)
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_what_is_missing_raises_and_says_so():
    model, params, _, _ = _model_and_params(CFG)
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="windowed layer.*24 keys"):
        model.apply({"params": params}, toks, decode=True, mutable=["cache"])
    with pytest.raises(ValueError, match="layer types"):
        FAMILY.model_kwargs(dict(CFG, layer_types=[WINDOWED, "linear"] * 3),
                            True)
    with pytest.raises(ValueError, match="windows"):
        TransformerLM(n_layers=2, layer_windows=(8,)).init(
            jax.random.PRNGKey(0), toks)


def test_a_windowed_layer_records_its_tiles_in_a_traced_step():
    """A train step that traces a windowed layer leaves one row a layer in
    the compile account: shapes, blocks, and the tiles computed beside those
    the band needs and those a causal call computes; a full layer none."""
    from dtdl_tpu.runtime import compile_cache
    from dtdl_tpu.train.state import TrainState
    import optax
    cfg = _layers(CFG, (WINDOWED, FULL, WINDOWED), 1)
    model, params, _, _ = _model_and_params(cfg, dtype=jnp.bfloat16)
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.adamw(3e-4))
    before = len(compile_cache.window_calls())
    make_lm_train_step(SingleDevice(), vocab_chunk_size=0).lower(
        state, {"tokens": jnp.zeros((2, ROW), jnp.int32)})
    rows = compile_cache.window_calls()[before:]
    assert rows and len(rows) % 2 == 0      # two windowed layers a trace
    for row in rows:
        assert row["fun_name"] == "lm_train_step"
        assert row["shapes"] == (2, 4, ROW - 1, 8) and row["window"] == 24
        # one block spans the 69 positions: one tile, the band's and causal's
        assert (row["block_q"], row["block_k"]) == (ROW - 1, ROW - 1)
        assert row["computed_tiles"] == row["needed_tiles"] == 1
        assert row["band_pairs"] == 24 * 25 // 2 + (69 - 24) * 24
    totals = compile_cache.compile_totals()
    assert totals["swa_calls"] == len(compile_cache.window_calls())


# digests of lowered CPU programs as the parent of PR 35 lowers them: the
# rehearsal steps of the three accepted families' cells.  The keywords of
# PR 35 at their defaults (no window, every layer rotated, no output norms,
# no embedding factor, the gate from a doubled q) and the flash kernels
# without a window must leave them as they were.
_PARENT_TEXT = {
    "qwen3next-train-share16":
        "e581f2f1127e94dd750dee53e7052afbe43d0492497fc20fe12818c695f7cfee",
    "olmo1b-train-b4s2048":
        "330fc9d151fc7f91c083596264d636daa3de94bc6d05911d490d1adfe5fc8a31",
    "kimilinear-train-share32":
        "d284650d8e25d37835d2be9bca4450aef658523858c5ac33ee529d1561e37655",
    "olmo7b-train-b2s2048":
        "3f70659d26fba6cc24ea77f2e0803423f0cf072067ff17648a1e77f284eb39f6",
}


@pytest.mark.parametrize("cell_name", ["qwen3next-train-share16",
                                       "olmo1b-train-b4s2048",
                                       "kimilinear-train-share32",
                                       "olmo7b-train-b2s2048"])
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
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT_TEXT[cell_name]
