"""The hybrid blocks of ``TransformerLM`` (Gated DeltaNet layers, gated
grouped-query attention, one chip's share of an expert layer, an untied
head) against the benchmark's plain reference of the same architecture
(``benchmarks/families/qwen3_next.py``, which imports nothing of the
program), on seeded weights at a small size; the share test; the overflow
that is never a silent drop; the two sizes of the experts' buffer, and that
the choice between them changes no number; the grouped-matmul kernels under
the interpreter; and that the model's defaults are the dense model, bit for
bit.
"""

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

from dtdl_tpu.models import remat_plan                        # noqa: E402
from dtdl_tpu.models.transformer import (GdnSpec, HeldExperts,  # noqa: E402
                                         HeldSpec, TransformerLM,
                                         _SharedExpert)
from dtdl_tpu.obs import goodput                              # noqa: E402
from dtdl_tpu.ops import grouped_matmul as gm                 # noqa: E402
from dtdl_tpu.ops.grouped_matmul import (ROW_TILE, first_buffer_rows,  # noqa: E402
                                         grouped_matmul, held_buffer_rows,
                                         moe_gmm, moe_tgmm, rows_of,
                                         weighted_rows_sum)
from dtdl_tpu.parallel.strategy import SingleDevice           # noqa: E402
from dtdl_tpu.train import make_lm_train_step                 # noqa: E402
from dtdl_tpu.train.state import TrainState                   # noqa: E402

FAMILY = modules.load_file(str(BENCH / "families" / "qwen3_next.py"),
                           "families")

# every mechanism of the architecture at a size the CPU runs in seconds:
# one period (3 linear + 1 full), 2 K/V heads under 4 query heads at a head
# size that is not hidden / heads, a quarter of the head rotated, 4 of 16
# experts held from id 4 on, 3 a token, a shared expert, an untied head
CFG = dict(
    model_type="qwen3_next", decoder_sparse_step=1, full_attention_interval=4,
    head_dim=16, hidden_size=32, intermediate_size=64,
    linear_conv_kernel_dim=4, linear_key_head_dim=8, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_value_head_dim=8,
    max_position_embeddings=256, mlp_only_layers=[], moe_intermediate_size=24,
    norm_topk_prob=True, num_attention_heads=4, num_experts=4,
    router_num_experts=16, first_expert_held=4, num_experts_per_tok=3,
    num_hidden_layers=4, num_key_value_heads=2, partial_rotary_factor=0.25,
    rms_norm_eps=1e-6, rope_theta=1e7, shared_expert_intermediate_size=24,
    tie_word_embeddings=False, vocab_size=96)
ROW = 70        # 69 positions: two chunks of the delta rule, the second ragged


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


def _program_loss(model, params, toks):
    """The train step's loss: the mean cross entropy, and nothing beside it
    (the expert layers sow no ``aux_loss``)."""
    logits, muts = model.apply({"params": params}, toks[:, :-1],
                               mutable=["moe_stats", "aux_loss"])
    assert "aux_loss" not in muts
    lse = jax.nn.logsumexp(logits, -1)
    true = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - true), muts


@pytest.mark.parametrize("kinds", [
    ("linear",), ("full",), ("linear", "linear", "linear", "full")],
    ids=["gated_delta_net", "gated_attention", "one_period"])
def test_program_equals_the_plain_reference_on_loss_and_every_gradient(kinds):
    """One layer of each kind (each with its expert layer), then the whole
    period: the loss and every leaf's gradient, float32 on both sides."""
    cfg = dict(CFG, num_hidden_layers=len(kinds),
               full_attention_interval=(4 if "full" in kinds
                                        and len(kinds) > 1
                                        else 1 if kinds == ("full",) else 9))
    assert FAMILY._layer_kinds(cfg) == kinds
    model, params, made, paths = _model_and_params(cfg)
    toks = jnp.asarray(tokens.batch_tokens(5, 0, 2, ROW, cfg["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        (loss, muts), grads = jax.value_and_grad(
            lambda p: _program_loss(model, p, toks), has_aux=True)(params)
        ref_loss, ref_grads = jax.jit(
            lambda p, t: FAMILY.loss_and_grads(p, t, cfg, "f32"))(made, toks)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    stats = muts["moe_stats"]
    assert len(jax.tree.leaves(stats)) == 3 * len(kinds)
    assert all(int(layer["moe"]["overflow_rows"][0]) == 0
               for layer in stats.values())
    grads = dict(zip(paths, jax.tree.leaves(grads)))
    assert set(grads) == set(ref_grads)
    for path in paths:
        want = np.asarray(ref_grads[path])
        gap = np.linalg.norm(np.asarray(grads[path]) - want)
        assert gap <= 5e-4 * max(np.linalg.norm(want), 1e-6), path


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The share test: each of 16 shares of a 32-expert layer holds 2
    experts and computes its own experts' part; their sum, with the shared
    expert counted once, is what the plain reference gives for the whole
    layer (all 32 held).  One compiled share serves all 16: share ``s`` of
    the layer is the share at id 0 of the layer whose router columns are
    rolled by ``-2 s`` (the same experts chosen, under other ids); one
    share is also run as the model states it, from its own first id."""
    d, ff, width, held, top_k = 16, 12, 32, 2, 4
    cfg = dict(CFG, hidden_size=d, moe_intermediate_size=ff,
               shared_expert_intermediate_size=ff, router_num_experts=width,
               num_experts=width, first_expert_held=0,
               num_experts_per_tok=top_k)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (2, 40, d))

    def layer(first, n_held):
        return HeldExperts(width, first, n_held, top_k, ff, ff,
                           dtype=jnp.float32)

    params = nn.unbox(layer(0, width).init(keys[1], x)["params"])
    params = jax.tree.map(
        lambda p: jax.random.normal(keys[2], p.shape) / np.sqrt(p.shape[-2]),
        params)
    flat = {_leaf_path(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}

    def mine(first, roll):
        router = jnp.roll(params["router"]["kernel"], -roll, axis=1)
        return dict(params, router={"kernel": router}, experts=jax.tree.map(
            lambda w: w[first:first + held], params["experts"]))

    share_at_0 = jax.jit(lambda p: layer(0, held).apply(
        {"params": p}, x, mutable=["moe_stats", "aux_loss"]))
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda row: FAMILY._experts(
            row, {"moe/" + k: v for k, v in flat.items()}, cfg, "f32"))(x)
        shared = _SharedExpert(ff, jnp.float32).apply(
            {"params": params["shared"]}, x.reshape(-1, d)).reshape(x.shape)
        total = shared
        for share in range(width // held):
            out, muts = share_at_0(mine(share * held, share * held))
            assert int(muts["moe_stats"]["overflow_rows"][0]) == 0
            assert "aux_loss" not in muts
            total = total + (out - shared)
            if share == 5:      # as the model states a share: from its id
                direct, _ = layer(10, held).apply(
                    {"params": mine(10, 0)}, x,
                    mutable=["moe_stats", "aux_loss"])
                assert float(jnp.max(jnp.abs(direct - out))) < 1e-5
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def _tiny_state(model, params):
    return TrainState.create(apply_fn=model.apply, params=params,
                             tx=optax.adamw(3e-4))


def test_overflow_makes_the_loss_non_finite_and_counts(monkeypatch):
    """A buffer too small for what was routed is an error: the step's loss
    is not finite and ``moe_overflow_rows`` counts the assignments left
    out (with room it reads 0: the test above).  At the stated multiple the
    buffer holds every assignment the shapes allow, so the overflow is
    planted by a smaller one."""
    monkeypatch.setattr(gm, "ROWS_MULTIPLE", 0.1)
    cfg = dict(CFG, num_hidden_layers=1, full_attention_interval=1,
               router_num_experts=4, num_experts=2, first_expert_held=1,
               num_experts_per_tok=4)        # every token takes both held
    toks = jnp.asarray(tokens.batch_tokens(9, 0, 4, ROW, cfg["vocab_size"]))
    model, params, _, _ = _model_and_params(cfg)
    _, metrics = make_lm_train_step(SingleDevice())(
        _tiny_state(model, params), {"tokens": toks})
    metrics = jax.device_get(metrics)
    n = 4 * (ROW - 1)
    rows, expected = held_buffer_rows(n, 4, 2, 4)
    # a tenth of the 2 n expected is one tile, and one more an expert; each
    # expert's n rows need three, so the first fits and the second does not
    assert (rows, expected) == (3 * ROW_TILE, 2 * n)
    assert metrics["moe_overflow_rows"] == n
    assert metrics["moe_live_rows"] == 2 * 3 * ROW_TILE
    assert not np.isfinite(metrics["loss"])


def _primitives(jaxpr, out=None):
    """The names of a jaxpr's primitives, those of its sub-jaxprs too."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for value in eqn.params.values():
            items = value if isinstance(value, (tuple, list)) else (value,)
            for item in items:
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


@pytest.mark.parametrize("tokens_, top_k, held, width, first, full", [
    (8190, 10, 32, 512, 45056, 86016), (276, 4, 2, 4, None, 896),
    (80, 3, 4, 16, None, 768), (276, 2, 4, 64, 896, 1152)])
def test_the_buffer_holds_every_assignment_the_shapes_allow(tokens_, top_k,
                                                            held, width,
                                                            first, full):
    """At the stated multiple no routing overflows: the full buffer's rows
    are every choice of every token in whole tiles and a tile an expert,
    which the worst split of the assignments over the experts still fits.
    The first buffer (``first``; None: as large as the full one, so there is
    one buffer) is 8 times the expected rows in whole tiles and a tile an
    expert, and the traced layer chooses between two only where it has
    two."""
    shapes = (tokens_, top_k, held, width)
    rows, expected = held_buffer_rows(*shapes)
    assert expected == tokens_ * top_k * held / width
    most = tokens_ * min(top_k, held)
    assert gm.ROWS_MULTIPLE * expected >= most
    assert rows == (-(-most // ROW_TILE) + held) * ROW_TILE == full
    # the worst case for alignment: every expert one row into a new tile
    counts = np.full(held, most // held)
    counts[:most % held] += 1
    assert sum(max(1, -(-c // ROW_TILE)) for c in counts) * ROW_TILE <= rows
    assert first_buffer_rows(*shapes) == (first or full)
    if first:
        assert first < full and first == ROW_TILE * (
            int(np.ceil(gm.FIRST_MULTIPLE * expected / ROW_TILE)) + held)
    layer = HeldExperts(width, 0, held, top_k, 8, dtype=jnp.float32)
    x = jnp.zeros((1, tokens_, 8))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]
    traced = _primitives(jax.make_jaxpr(lambda p: layer.apply(
        {"params": p}, x, mutable=["moe_stats"]))(params).jaxpr)
    assert "pallas_call" in traced
    assert ("cond" in traced) == bool(first)


# top 2 of 64 with 4 held, 4 x 69 tokens: 34.5 assignments expected, a full
# buffer of 9 tiles and a first of 7; two full-attention layers
_TWO_BUFFERS = dict(CFG, router_num_experts=64, num_experts=4,
                    first_expert_held=8, num_experts_per_tok=2,
                    num_hidden_layers=2, full_attention_interval=1)


def _lean_to_the_held(params, bias):
    """``params`` with the first feature of every embedding raised by ``2
    bias`` and the first row of every router raised by ``bias`` in the
    columns of the held experts 10 and 11, the last two: the residual
    stream's first feature is then large in every token, and every token
    chooses those two."""
    def lean(path, leaf):
        if _leaf_path(path).endswith("router/kernel"):
            return leaf.at[0, 10:12].add(bias)
        if _leaf_path(path) == "embed":
            return leaf.at[:, 0].add(2 * bias)
        return leaf
    return jax.tree_util.tree_map_with_path(lean, params)


@pytest.mark.parametrize("bias, full_layers", [(0.0, 0), (4.0, 2)],
                         ids=["fits_the_first", "takes_the_full"])
def test_the_choice_of_buffer_changes_no_number(monkeypatch, bias,
                                                full_layers):
    """Two buffers against one (the first multiple raised to the full one's,
    which is the program before there was a choice), with every layer's
    routing inside the first buffer or, the routers biased towards two of
    the held experts, none: a train step's loss is the one-buffer program's
    and the step counts the layers that took the full buffer; a layer's
    output and every gradient are the one-buffer layer's.  Two programs that
    XLA:CPU fuses differently agree to float32's rounding, not to the bit
    (the shared expert's gate, which no buffer touches, differs in its last
    bit between them); so the bits are compared where the buffer's size is
    the only difference: the layer's own operands, taken from the one-buffer
    layer, through the grouped SwiGLU at both sizes, value and every
    cotangent.  A routing that fits gives the same bits; one that does not
    would have lost rows to the first buffer, and took the full one."""
    from dtdl_tpu.models import transformer
    cfg = _TWO_BUFFERS
    tokens_, d = 4 * (ROW - 1), cfg["hidden_size"]
    shapes = (tokens_, 2, 4, 64)
    first, full = 7 * ROW_TILE, 9 * ROW_TILE
    model, params, _, _ = _model_and_params(cfg)
    params = _lean_to_the_held(params, bias)
    toks = jnp.asarray(tokens.batch_tokens(11, 0, 4, ROW, cfg["vocab_size"]))
    layer = HeldExperts(64, 8, 4, 2, 24, 24, dtype=jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(keys[0], (4, ROW - 1, d)).at[..., 0].add(2 * bias)
    layer_params = _lean_to_the_held(jax.tree.map(
        lambda p: jax.random.normal(keys[1], p.shape) / np.sqrt(p.shape[-2]),
        nn.unbox(layer.init(keys[2], x)["params"])), bias)

    def run():
        _, metrics = make_lm_train_step(SingleDevice())(
            _tiny_state(model, jax.tree.map(jnp.copy, params)),
            {"tokens": toks})               # the step donates its state

        def loss(p, x):
            out, muts = layer.apply({"params": p}, x, mutable=["moe_stats"])
            return jnp.sum(out * jnp.cos(out)), (out, muts["moe_stats"])
        (_, (out, stats)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(layer_params, x)
        return jax.device_get(metrics), out, stats, grads

    assert first_buffer_rows(*shapes) == first
    assert held_buffer_rows(*shapes)[0] == full
    metrics, out, stats, grads = run()
    monkeypatch.setattr(gm, "FIRST_MULTIPLE", gm.ROWS_MULTIPLE)
    assert first_buffer_rows(*shapes) == full
    metrics1, out1, stats1, grads1 = run()

    assert metrics["moe_full_buffer_layers"] == full_layers
    assert metrics1["moe_full_buffer_layers"] == 0
    assert metrics["moe_live_rows"] == metrics1["moe_live_rows"]
    assert (metrics["moe_live_rows"] > first) == bool(full_layers)
    assert metrics["moe_overflow_rows"] == 0
    assert int(stats["full_buffer"][0]) == bool(full_layers)
    assert int(stats1["full_buffer"][0]) == 0
    assert (int(stats["live_rows"][0]) > first) == bool(full_layers)

    def same(a, b, what):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b) > 0, what

    same(metrics["loss"], metrics1["loss"], "the step's loss")
    same(out, out1, "the layer's output")
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(grads1)):
        same(a, b, jax.tree_util.keystr(path))

    # the layer's own operands through both sizes, to the bit
    swiglu, calls = transformer._grouped_swiglu, []
    monkeypatch.setattr(transformer, "_grouped_swiglu", lambda *a: (
        calls.append(a), swiglu(*a))[1])
    layer.apply({"params": layer_params}, x, mutable=["moe_stats"])
    (xf, gates, weights, plan, n_rows), = calls
    assert n_rows == full
    got = {}
    for n in (first, full):
        y, pull = jax.vjp(jax.jit(lambda *a, n=n: swiglu(*a, plan, n)),
                          xf, gates, weights)
        got[n] = jax.tree.leaves((y, pull(jnp.cos(y))))
    equal = [np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(got[first], got[full])]
    assert len(equal) == 6
    assert all(equal) if not full_layers else not any(equal[:3])


def test_the_way_in_and_out_of_the_buffer_equals_a_scatter_add():
    """``rows_of`` and ``weighted_rows_sum`` (gathers, with gathers for
    gradients) against the plain take and scatter-add they replace, values
    and every gradient, with rows no assignment has and assignments no row
    has."""
    tokens_, top_k, n_rows, d = 12, 3, 24, 8
    rng = np.random.default_rng(0)
    # 20 of the 36 assignments get a row each, in a shuffled order
    have = rng.permutation(tokens_ * top_k)[:20]
    at = rng.permutation(n_rows)[:20]
    row_assign = np.full(n_rows, tokens_ * top_k)
    row_assign[at] = have
    assign_row = np.full(tokens_ * top_k, n_rows)
    assign_row[have] = at
    row_assign = jnp.asarray(row_assign, jnp.int32)
    assign_row = jnp.asarray(assign_row.reshape(tokens_, top_k), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(keys[0], (tokens_, d))
    w = jax.random.normal(keys[1], (d, d))
    gates = jax.random.uniform(keys[2], (tokens_, top_k))
    d_out = jax.random.normal(keys[3], (tokens_, d))

    def plain(x, gates):
        xpad = jnp.concatenate([x, jnp.zeros((1, d))])
        row_token = row_assign // top_k
        y = jnp.tanh(xpad[row_token] @ w)
        row_gate = jnp.concatenate([gates.reshape(-1), jnp.zeros(1)])[
            row_assign]
        return jnp.zeros((tokens_ + 1, d)).at[row_token].add(
            y * row_gate[:, None])[:tokens_]

    def gathers(x, gates):
        y = jnp.tanh(rows_of(x, row_assign, assign_row) @ w)
        return weighted_rows_sum(y, gates, row_assign, assign_row)

    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(plain, x, gates)
        got, pull_g = jax.vjp(gathers, x, gates)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-6
        for a, b in zip(pull_g(d_out), pull(d_out)):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_grouped_matmul_kernels_equal_a_loop_of_matmuls(dtype):
    """``moe_gmm`` (plain and transposed) and ``moe_tgmm`` under the
    interpreter against one matmul an expert, and the custom VJP against
    JAX's own gradient of that loop."""
    experts, k, n = 3, 32, 48
    tile_expert = jnp.array([0, 0, 1, 2, 2, 2], jnp.int32)
    rows = tile_expert.size * ROW_TILE
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (rows, k)).astype(dtype)
    w = jax.random.normal(keys[1], (experts, k, n)).astype(dtype)
    dy = jax.random.normal(keys[2], (rows, n)).astype(dtype)
    row_expert = np.repeat(np.asarray(tile_expert), ROW_TILE)

    def loop(x, w):
        out = jnp.zeros((rows, n), jnp.float32)
        for e in range(experts):
            mine = jnp.asarray(row_expert == e)[:, None]
            out = out + jnp.where(mine, jnp.dot(
                x.astype(jnp.float32), w[e].astype(jnp.float32),
                precision="highest"), 0.0)
        return out

    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    with jax.default_matmul_precision("highest"):
        want = loop(x, w)
        got = moe_gmm(x, w, tile_expert)
        assert got.dtype == dtype
        assert float(jnp.max(jnp.abs(got - want))) < tol * float(
            jnp.max(jnp.abs(want)))
        dx_want, dw_want = jax.vjp(loop, x, w)[1](dy.astype(jnp.float32))
        dx = moe_gmm(dy, w, tile_expert, transpose_rhs=True)
        dw = moe_tgmm(x, dy, tile_expert, experts)
        assert dw.dtype == jnp.float32
        for a, b in ((dx, dx_want), (dw, dw_want)):
            assert float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                         - b.astype(jnp.float32)))) \
                < tol * float(jnp.max(jnp.abs(b.astype(jnp.float32))))
        gx, gw = jax.vjp(lambda x, w: grouped_matmul(x, w, tile_expert),
                         x, w)[1](dy)
        assert gx.dtype == gw.dtype == dtype
        assert float(jnp.max(jnp.abs(gx.astype(jnp.float32)
                                     - dx.astype(jnp.float32)))) == 0.0


# digests of the lowered CPU rehearsal step of ``olmo1b-train-b4s2048``
# (``run.py --rehearse``'s stand-in, dense head and chunked loss) as the
# parent of PR 29 lowers it: the hybrid keywords at their defaults must
# leave the dense model's program as it was.  A change that means to alter
# the dense step computes them anew (the loop below prints them on failure).
# Computed anew when flash attention's backward became one kernel.
_OLMO_REHEARSAL_STEP = {
    0: "89e9235c315c4cf1f5ad1c11758de65e8641877ee209b1db8f9543ecf0b4dfc1",
    64: "8f4c134aede292f4774fd89dcfb201e848d7a7123ef2df0311e97423958aab5f",
}


def test_defaults_leave_the_dense_model_and_its_lowered_step_unchanged():
    import run as harness
    from runners import train
    manifest = harness.load_json(str(BENCH.parent / "BENCHMARK.json"))
    cell, cfg = harness.resolve(manifest, "olmo1b-train-b4s2048", True)
    plan = train.make_plan(cell, cfg)
    assert not plan.model.hybrid and plan.model.tie_embeddings
    assert sorted(plan.shapes) == sorted(
        ["embed", "ln_f/scale"] + [
            f"block_{i}/{leaf}" for i in range(cfg["num_hidden_layers"])
            for leaf in ("attn/q/kernel", "attn/k/kernel", "attn/v/kernel",
                         "attn/out/kernel", "ln_attn/scale", "ln_mlp/scale",
                         "mlp/wi/kernel", "mlp/wg/kernel", "mlp/wo/kernel")])
    state = jax.eval_shape(plan.build, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cell["batch_per_chip"], cell["row_tokens"]), jnp.int32)}
    for chunk, digest in _OLMO_REHEARSAL_STEP.items():
        text = make_lm_train_step(
            SingleDevice(), vocab_chunk_size=chunk).lower(state,
                                                          batch).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() == digest, chunk


def test_what_is_missing_raises_and_says_so():
    model, params, _, _ = _model_and_params(CFG)
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="trains only"):
        model.apply({"params": params}, toks, decode=True,
                    mutable=["cache"])
    with pytest.raises(NotImplementedError, match="trains only"):
        model.init(jax.random.PRNGKey(0), toks, decode=True)
    step = make_lm_train_step(SingleDevice(), vocab_chunk_size=32)
    with pytest.raises(ValueError, match="head table of its own"):
        step.lower(_tiny_state(model, params),
                   {"tokens": jnp.zeros((2, ROW), jnp.int32)})
    with pytest.raises(ValueError, match="layer kinds"):
        TransformerLM(n_layers=2, layer_kinds=("full",)).init(
            jax.random.PRNGKey(0), toks)


@pytest.mark.parametrize("count", ["lm_train_flops", "lm_forward_flops",
                                   "lm_decode_flops"])
def test_goodput_refuses_the_dense_formula_for_a_hybrid_model(count):
    """``obs/goodput.py`` never reports the dense formula for a model with
    linear-attention or held-expert layers: it says where the count is."""
    model = TransformerLM(**FAMILY.model_kwargs(CFG, False))
    with pytest.raises(ValueError, match="hybrid model"):
        getattr(goodput, count)(model, 2, ROW)
    dense = TransformerLM(vocab_size=96, d_model=32, n_layers=4, n_heads=4,
                          d_ff=64)
    assert getattr(goodput, count)(dense, 2, ROW) > 0


def test_the_plan_reckons_each_blocks_own_bytes(monkeypatch):
    """With a limit the one full-attention block may keep the attention
    names at its own head width, a linear block what the delta rule's loop
    reads and the ``in_qkvz`` output (the rule's ``T`` has no name since the
    kernels hold it in VMEM); the estimate grows with the projection widths
    and the experts' buffer, not with ``d_ff``."""
    from dtdl_tpu.runtime import compile_cache
    monkeypatch.setattr(remat_plan, "device_bytes_limit", lambda: 10 ** 9)
    model, params, _, _ = _model_and_params(CFG, dtype=jnp.bfloat16)
    step = make_lm_train_step(SingleDevice())
    step.lower(_tiny_state(model, params),
               {"tokens": jnp.zeros((2, ROW), jnp.int32)})
    plan = compile_cache.remat_plans()[-1]
    assert plan.rungs == (2, 2, 2, 2)       # everything a block can keep
    t, width = 2 * (ROW - 1), 4 * 16
    linear = remat_plan.gdn_residual_bytes(2, ROW - 1, 32,
                                           GdnSpec(2, 4, 8, 8, 4), 2)
    assert linear == (2 * 128 * 4 * (3 * 8 + 8 + 64) * 2,  # two chunks a row
                      t * (2 * 2 * 8 + 2 * 4 * 8) * 2, 0)
    full = remat_plan.residual_bytes(2, ROW - 1, 32, 4, 0, 2,
                                     attn_width=width)
    assert full == (t * width * 2 + 2 * 4 * (ROW - 1) * 4,
                    (3 * width + 32) * t * 2, 0)
    assert plan.kept_bytes == 3 * sum(linear) + sum(full)
    assert remat_plan.saved_names(1, linear=True, held=True) == (
        "gdn_loop", "moe_plan")
    assert remat_plan.saved_names(2, linear=True) == ("gdn_loop", "gdn_in")
    assert remat_plan.saved_names(0, held=True) == ("moe_plan",)
    assert remat_plan.policy(0) is None
    buffer = compile_cache.expert_buffers()[-1]
    rows, expected = held_buffer_rows(t, 3, 4, 16)
    assert buffer == {"fun_name": "lm_train_step", "rows": rows,
                      "row_tile": ROW_TILE, "expected_rows": expected,
                      "first_rows": first_buffer_rows(t, 3, 4, 16)}
    totals = compile_cache.compile_totals()
    assert totals["moe_buffer_rows"] == rows
    assert totals["moe_first_buffer_rows"] == rows      # one buffer here
    live = remat_plan.hybrid_block_live_bytes
    assert live(2, 69, 32, 2, gdn=GdnSpec(2, 4, 8, 8, 4)) < \
        live(2, 69, 32, 2, gdn=GdnSpec(2, 8, 8, 8, 4))
    assert live(2, 4095, 32, 2, held=HeldSpec(16, 0, 4, 2, 24, 24)) < \
        live(2, 4095, 32, 2, held=HeldSpec(16, 0, 4, 3, 24, 24))
