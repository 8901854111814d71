"""The chunkwise gated delta rule (ops/gated_delta.py) against the
token-by-token recurrence: values and gradients, at lengths that are no
multiple of the chunk, with decays near 1, and under ``jax.checkpoint``;
on both implementations of its chunk-local stage, the ``jax.numpy`` one
(head sizes 16 and 24) and the Pallas kernels under the interpreter (head
sizes of 128: ``KERNEL``), and the two against each other."""

import jax
import jax.numpy as jnp
import pytest

from dtdl_tpu.models import remat_plan
from dtdl_tpu.ops import gated_delta
from dtdl_tpu.ops.gated_delta import (_inv_unit_lower, gated_delta_recurrence,
                                      gated_delta_rule, stage_plan)
from dtdl_tpu.runtime import compile_cache

JNP = dict(heads=3, dk=16, dv=24)           # shapes that fall to jax.numpy
# shapes the kernels take: one key head that serves two value heads
KERNEL = dict(batch=1, heads=2, key_heads=1, dk=128, dv=128)


def _inputs(length, decay, heads=3, dk=16, dv=24, seed=0, key_heads=None,
            batch=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, length, heads)
    q = jax.random.normal(keys[0], (batch, length, key_heads or heads, dk))
    k = jax.random.normal(keys[1], (batch, length, key_heads or heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], shape + (dv,))
    # the log of a decay around ``decay`` a token
    g = jnp.log(decay) * jax.nn.softplus(1.0 + jax.random.normal(
        keys[3], shape)) / 1.31
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    """The oracle, each key head repeated to the value heads it serves."""
    ratio = v.shape[2] // q.shape[2]
    return gated_delta_recurrence(jnp.repeat(q, ratio, axis=2),
                                  jnp.repeat(k, ratio, axis=2), v, g, beta)


def _scalar(fn):
    return lambda *a: jnp.sum(jnp.sin(fn(*a)))


def _the_jnp_path(monkeypatch):
    """Every shape falls to the ``jax.numpy`` stage from here on."""
    monkeypatch.setattr(
        gated_delta, "stage_plan",
        lambda dk, dv, chunk=None: ("jnp", chunk or gated_delta.JNP_CHUNK))


def _takes_the_kernels(fn, *args):
    return "gdn_chunk_fwd" in str(jax.make_jaxpr(fn)(*args))


# ``chunk`` None: the path's own (128 for the kernels, 64 otherwise)
@pytest.mark.parametrize("length, chunk, decay, shapes", [
    (150, 64, 0.99, JNP), (64, 64, 0.9, JNP), (37, 16, 0.999, JNP),
    (130, 32, 0.5, JNP), (5, 8, 0.99, JNP),
    (150, 64, 0.99, KERNEL), (200, None, 0.999, KERNEL)],
    ids=lambda x: "kernel" if x is KERNEL else "jnp" if x is JNP else str(x))
def test_chunked_rule_equals_the_recurrence(length, chunk, decay, shapes,
                                            monkeypatch):
    args = _inputs(length, decay, **shapes)

    def rule(*a):
        return gated_delta_rule(*a, chunk=chunk)

    assert _takes_the_kernels(rule, *args) == (shapes is KERNEL)
    with jax.default_matmul_precision("highest"):
        got, want = rule(*args), _recurrence(*args)
        _the_jnp_path(monkeypatch)
        other = rule(*args)
    assert got.shape == want.shape == args[2].shape
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * top
    assert float(jnp.max(jnp.abs(got - other))) < 2e-5 * top


# ``rung``: under ``jax.checkpoint`` with what a linear block keeps at it
@pytest.mark.parametrize("length, chunk, decay, rung, shapes", [
    (150, 64, 0.99, None, JNP), (70, 32, 0.999, 0, JNP),
    (70, 32, 0.999, 1, JNP),
    (150, 64, 0.99, None, KERNEL), (150, None, 0.999, 0, KERNEL),
    (150, None, 0.999, 1, KERNEL), (70, 64, 0.9, 2, KERNEL)],
    ids=lambda x: "kernel" if x is KERNEL else "jnp" if x is JNP else str(x))
def test_chunked_rule_gradients_equal_the_recurrences(length, chunk, decay,
                                                      rung, shapes,
                                                      monkeypatch):
    args = _inputs(length, decay, seed=1, **shapes)

    def plain(*a):
        return gated_delta_rule(*a, chunk=chunk)

    chunked = plain if rung is None else jax.checkpoint(
        plain, policy=remat_plan.policy(rung, linear=True))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(_scalar(chunked), argnums=range(5))(*args)
        want = jax.grad(_scalar(_recurrence), argnums=range(5))(*args)
        other = want
        if shapes is KERNEL:
            _the_jnp_path(monkeypatch)
            other = jax.grad(_scalar(plain), argnums=range(5))(*args)
    for name, a, b, c in zip("q k v g beta".split(), got, want, other):
        top = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * top, name
        assert float(jnp.max(jnp.abs(a - c))) < 1e-4 * top, name


@pytest.mark.parametrize("shapes", [JNP, KERNEL], ids=["jnp", "kernel"])
def test_bf16_operands_stay_near_the_recurrence(shapes, monkeypatch):
    """``operand_dtype=bfloat16`` (what the model passes on the chip): every
    matmul's operands rounded, float32 sums and state; values and gradients
    within bfloat16's rounding of the float32 recurrence, and the kernels
    within it of the ``jax.numpy`` stage."""
    args = _inputs(150, 0.99, seed=4, **shapes)

    def rounded(*a):
        return gated_delta_rule(*a, operand_dtype=jnp.bfloat16)

    assert _takes_the_kernels(rounded, *args) == (shapes is KERNEL)
    with jax.default_matmul_precision("highest"):
        got, want = rounded(*args), _recurrence(*args)
        g_got = jax.grad(_scalar(rounded), argnums=(0, 1, 2))(*args)
        g_want = jax.grad(_scalar(_recurrence), argnums=(0, 1, 2))(*args)
        _the_jnp_path(monkeypatch)
        other = rounded(*args)
    assert got.dtype == jnp.float32
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2 * top
    assert float(jnp.max(jnp.abs(got - other))) < 3e-2 * top
    for a, b in zip(g_got, g_want):
        assert float(jnp.linalg.norm(a - b)) < 3e-2 * float(
            jnp.linalg.norm(b))


@pytest.mark.parametrize("chunk, shapes", [(16, JNP), (64, KERNEL)],
                         ids=["jnp", "kernel"])
def test_padding_rows_change_nothing_and_the_state_crosses_chunks(chunk,
                                                                  shapes):
    """A prefix's output does not depend on what follows it, and an early
    token still shows many chunks later when the decay is near 1 (a fault
    in the pass between chunks would lose it)."""
    q, k, v, g, beta = _inputs(200, 0.999, seed=2, **shapes)
    with jax.default_matmul_precision("highest"):
        whole = gated_delta_rule(q, k, v, g, beta, chunk=chunk)
        prefix = gated_delta_rule(q[:, :77], k[:, :77], v[:, :77],
                                  g[:, :77], beta[:, :77], chunk=chunk)
        moved = gated_delta_rule(q, k, v.at[:, 0].add(1.0), g, beta,
                                 chunk=chunk)
    assert float(jnp.max(jnp.abs(whole[:, :77] - prefix))) < 1e-5
    assert float(jnp.max(jnp.abs(moved[:, 180:] - whole[:, 180:]))) > 1e-4


@pytest.mark.parametrize("n, scale", [(24, 0.3), (64, 0.95), (8, 1.0)])
def test_block_substitution_inverts_a_unit_lower_triangle(n, scale):
    """Also where every entry is near 1 (keys that point alike, ``beta``
    near 1): the nilpotent series' terms reach 1e17 there before they
    cancel; block substitution forms nothing larger than the inverse."""
    noise = jax.random.normal(jax.random.PRNGKey(3), (4, n, n))
    a = jnp.tril(scale * (1.0 + 0.05 * noise), -1)
    with jax.default_matmul_precision("highest"):
        t = _inv_unit_lower(a)
        eye = t @ (jnp.eye(n) + a)
    assert float(jnp.max(jnp.abs(t))) < 10
    assert float(jnp.max(jnp.abs(eye - jnp.eye(n)))) < 1e-4


@pytest.mark.parametrize("key_heads, dim", [(2, 16), (1, 128)],
                         ids=["jnp", "kernel"])
def test_keys_that_point_alike_stay_finite_and_right(key_heads, dim):
    """What gave NaN on the chip (PERF.md section 6, PR 29): keys nearly
    parallel over a whole chunk with ``beta`` near 1 and a decay near 1;
    each key head serves two value heads."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    heads = 2 * key_heads
    k = jax.random.normal(keys[0], (1, 1, key_heads, dim)) \
        + 0.1 * jax.random.normal(keys[1], (1, 200, key_heads, dim))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (1, 200, heads, dim))
    g = jnp.full((1, 200, heads), -0.005)
    beta = jnp.full((1, 200, heads), 0.95)
    with jax.default_matmul_precision("highest"):
        got = gated_delta_rule(k * 0.25, k, v, g, beta)
        want = _recurrence(k * 0.25, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_shapes_the_kernels_do_not_take_fall_to_the_jnp_path():
    """The path is a function of the head sizes and the chunk alone: whole
    lane widths at a chunk the kernels are written for, or ``jax.numpy``."""
    assert stage_plan(128, 128) == ("kernel", 128)
    assert stage_plan(256, 128, 64) == ("kernel", 64)
    assert stage_plan(16, 24) == ("jnp", 64)
    assert stage_plan(128, 64) == ("jnp", 64)        # one head size fits
    assert stage_plan(128, 128, 32) == ("jnp", 32)   # no kernel at this chunk
    small = _inputs(70, 0.9, **JNP)
    fits = _inputs(70, 0.9, **KERNEL)
    text = str(jax.make_jaxpr(jax.grad(_scalar(gated_delta_rule)))(*small))
    assert "pallas_call" not in text
    text = str(jax.make_jaxpr(jax.grad(_scalar(gated_delta_rule)))(*fits))
    assert "gdn_chunk_fwd" in text and "gdn_chunk_bwd" in text
    odd_chunk = str(jax.make_jaxpr(
        lambda *a: gated_delta_rule(*a, chunk=32))(*fits))
    assert "pallas_call" not in odd_chunk


@pytest.mark.parametrize("dim, path, chunk", [(8, "jnp", 64),
                                              (128, "kernel", 128)])
def test_the_account_names_the_path_a_traced_step_took(dim, path, chunk):
    """A linear-attention layer traced by a train step adds one row to the
    compile account (runtime/compile_cache.py:gdn_paths): the path its
    shapes chose, the chunk, the call's shapes.  Outside a step: none."""
    from dtdl_tpu.models.transformer import GatedDeltaNet
    layer = GatedDeltaNet(1, 2, dim, dim, dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((2, 70, 32), jnp.float32)
    before = len(compile_cache.gdn_paths())
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    assert len(compile_cache.gdn_paths()) == before
    with remat_plan.step_memory("lm_train_step", 0, None):
        jax.eval_shape(layer.apply, params, x)
    assert compile_cache.gdn_paths()[before:] == [
        {"fun_name": "lm_train_step", "path": path, "chunk": chunk,
         "shapes": (2, 70, 1, 2, dim, dim)}]
    assert compile_cache.compile_totals()[f"gdn_{path}_calls"] >= 1


# ---------------------------------------------------------------------------
# the channel-wise rule (Kimi Delta Attention): ``kda_rule``
# ---------------------------------------------------------------------------

KDA_JNP = dict(heads=2, dk=16, dv=24)
KDA_KERNEL = dict(batch=1, heads=2, dk=128, dv=128)


def _kda_inputs(length, decay, heads=2, dk=16, dv=24, seed=0, batch=2):
    """As ``_inputs``, the log of the decay a key channel: around ``decay``
    a token, each channel at a rate of its own."""
    q, k, v, _, beta = _inputs(length, decay, heads, dk, dv, seed,
                               batch=batch)
    noise = jax.random.normal(jax.random.PRNGKey(seed + 100),
                              (batch, length, heads, dk))
    g = jnp.log(decay) * jax.nn.softplus(1.0 + noise) / 1.31
    return q, k, v, g, beta


def _takes_the_kda_kernels(fn, *args):
    return "kda_chunk_fwd" in str(jax.make_jaxpr(fn)(*args))


# decays from 0.999 to 0.3 a token, chunks 64 and 128 on the kernels
@pytest.mark.parametrize("length, chunk, decay, shapes", [
    (150, 64, 0.99, KDA_JNP), (37, 16, 0.999, KDA_JNP),
    (130, 32, 0.3, KDA_JNP), (70, 8, 0.5, KDA_JNP),
    (150, 64, 0.9, KDA_KERNEL), (200, None, 0.3, KDA_KERNEL),
    (140, 128, 0.999, KDA_KERNEL), (130, 64, 0.3, KDA_KERNEL)],
    ids=lambda x: ("kernel" if x is KDA_KERNEL else "jnp" if x is KDA_JNP
                   else str(x)))
def test_channelwise_rule_equals_the_recurrence_in_values_and_gradients(
        length, chunk, decay, shapes):
    """Chunked against token by token: the output and the gradients of all
    five inputs (q, k, v, the decay a channel, beta), no value non-finite."""
    args = _kda_inputs(length, decay, **shapes)

    def rule(*a):
        return gated_delta.kda_rule(*a, chunk=chunk)

    assert _takes_the_kda_kernels(rule, *args) == (shapes is KDA_KERNEL)
    with jax.default_matmul_precision("highest"):
        got, want = rule(*args), gated_delta.kda_recurrence(*args)
        grads = jax.grad(_scalar(rule), argnums=range(5))(*args)
        wants = jax.grad(_scalar(gated_delta.kda_recurrence),
                         argnums=range(5))(*args)
    assert got.shape == want.shape == args[2].shape
    assert bool(jnp.all(jnp.isfinite(got)))
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * top
    for name, a, b in zip("q k v g beta".split(), grads, wants):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b))) \
            < 5e-5 * float(jnp.max(jnp.abs(b))), name


@pytest.mark.parametrize("shapes", [KDA_JNP, KDA_KERNEL],
                         ids=["jnp", "kernel"])
def test_with_equal_channels_the_channelwise_rule_is_the_scalar_one(shapes):
    q, k, v, g, beta = _kda_inputs(150, 0.95, **shapes)
    g = jnp.broadcast_to(g[..., :1], g.shape)
    with jax.default_matmul_precision("highest"):
        got = gated_delta.kda_rule(q, k, v, g, beta)
        want = gated_delta_rule(q, k, v, g[..., 0], beta)
    assert float(jnp.max(jnp.abs(got - want))) \
        < 2e-5 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("decay", [0.999, 0.9, 0.3, 0.01])
@pytest.mark.parametrize("chunk", [64, 128])
def test_no_positive_exponent_is_taken_at_any_decay(decay, chunk,
                                                    monkeypatch):
    """The bound the operator's comment states: every argument of ``exp``
    in a tile of the chunk-local stage is a difference ``Gamma_i - Gamma_j``
    with ``j <= i`` and so at most 0 (up to the rounding of two float32
    running sums), at decays down to 0.01 a token, where ``K e^-Gamma``
    would overflow float32 within 20 positions."""
    q, k, v, g, beta = (x[0, :, 0] for x in _kda_inputs(
        chunk, decay, heads=1, dk=16, dv=16, batch=1))
    largest = []

    class Recording:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def exp(x):
            largest.append(float(jnp.max(x)))
            return jnp.exp(x)

    monkeypatch.setattr(gated_delta, "jnp", Recording())
    outs = gated_delta._kda_tile(q, k, v, g, beta[:, None], None,
                                 gated_delta._TileOps(False, False))
    assert len(largest) == 7 + (chunk // 8).bit_length() - 1 + 2
    assert max(largest) <= 1e-4 * abs(float(jnp.sum(g[:, 0])))
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in outs)


def test_bf16_operands_keep_the_channelwise_rule_near_the_recurrence():
    args = _kda_inputs(200, 0.98, **KDA_KERNEL)
    want = gated_delta.kda_recurrence(*args)
    for chunk in (64, 128):
        got = gated_delta.kda_rule(*args, chunk=chunk,
                                   operand_dtype=jnp.bfloat16)
        assert float(jnp.max(jnp.abs(got - want))) \
            < 3e-2 * float(jnp.max(jnp.abs(want))), chunk


def test_the_channelwise_rule_takes_kernels_at_lane_sized_heads_alone():
    assert stage_plan(128, 128, channelwise=True) == ("kernel", 128)
    assert stage_plan(128, 128, 64, channelwise=True) == ("kernel", 64)
    assert stage_plan(16, 24, channelwise=True) == ("jnp", 64)
    assert stage_plan(128, 128, 32, channelwise=True) == ("jnp", 32)
    fits = _kda_inputs(70, 0.9, **KDA_KERNEL)
    text = str(jax.make_jaxpr(jax.grad(_scalar(gated_delta.kda_rule)))(*fits))
    assert "kda_chunk_fwd" in text and "kda_chunk_bwd" in text
    assert "gdn_chunk" not in text
    with pytest.raises(ValueError, match="as many key heads"):
        gated_delta.kda_rule(fits[0][:, :, :1], *fits[1:])
    with pytest.raises(ValueError, match="power of two"):
        gated_delta.kda_rule(*_kda_inputs(70, 0.9, **KDA_JNP), chunk=48)


def test_a_checkpointed_block_keeps_what_the_channelwise_loop_reads():
    """Under the ``kda_loop`` rung the chunk-local stage is not run again:
    the backward pass holds one forward kernel call, not two."""
    args = _kda_inputs(130, 0.9, **KDA_KERNEL)

    def calls(rung):
        fn = jax.checkpoint(gated_delta.kda_rule,
                            policy=remat_plan.policy(rung, linear="kda"))
        return str(jax.make_jaxpr(jax.grad(_scalar(fn)))(*args)).count(
            "name=kda_chunk_fwd")

    assert (calls(0), calls(1)) == (2, 1)


@pytest.mark.parametrize("dim, path, chunk", [(8, "jnp", 64),
                                              (128, "kernel", 128)])
def test_the_account_names_the_path_a_kda_layer_took(dim, path, chunk):
    from dtdl_tpu.models.transformer import KimiDeltaAttention
    layer = KimiDeltaAttention(2, dim, 4, dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((2, 70, 32), jnp.float32)
    before = len(compile_cache.kda_paths())
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    assert len(compile_cache.kda_paths()) == before
    with remat_plan.step_memory("lm_train_step", 0, None):
        jax.eval_shape(layer.apply, params, x)
    assert compile_cache.kda_paths()[before:] == [
        {"fun_name": "lm_train_step", "path": path, "chunk": chunk,
         "shapes": (2, 70, 2, dim, dim)}]
    assert compile_cache.compile_totals()[f"kda_{path}_calls"] >= 1
