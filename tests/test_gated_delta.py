"""The chunkwise gated delta rule (ops/gated_delta.py) against the
token-by-token recurrence: values and gradients, at lengths that are no
multiple of the chunk, with decays near 1, and under ``jax.checkpoint``."""

import jax
import jax.numpy as jnp
import pytest

from dtdl_tpu.ops.gated_delta import (_inv_unit_lower, gated_delta_recurrence,
                                      gated_delta_rule)


def _inputs(length, decay, heads=3, dk=16, dv=24, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (2, length, heads)
    q = jax.random.normal(keys[0], shape + (dk,))
    k = jax.random.normal(keys[1], shape + (dk,))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], shape + (dv,))
    # the log of a decay around ``decay`` a token
    g = jnp.log(decay) * jax.nn.softplus(1.0 + jax.random.normal(
        keys[3], shape)) / 1.31
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape))
    return q, k, v, g, beta


@pytest.mark.parametrize("length, chunk, decay", [
    (150, 64, 0.99), (64, 64, 0.9), (37, 16, 0.999), (130, 32, 0.5),
    (5, 8, 0.99)])
def test_chunked_rule_equals_the_recurrence(length, chunk, decay):
    args = _inputs(length, decay)
    with jax.default_matmul_precision("highest"):
        got = gated_delta_rule(*args, chunk=chunk)
        want = gated_delta_recurrence(*args)
    assert got.shape == want.shape == (2, length, 3, 24)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("length, chunk, decay, remat", [
    (150, 64, 0.99, False), (70, 32, 0.999, True)])
def test_chunked_rule_gradients_equal_the_recurrences(length, chunk, decay,
                                                      remat):
    args = _inputs(length, decay, seed=1)

    def chunked(*a):
        return gated_delta_rule(*a, chunk=chunk)

    if remat:
        chunked = jax.checkpoint(chunked)

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(scalar(chunked), argnums=range(5))(*args)
        want = jax.grad(scalar(gated_delta_recurrence),
                        argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * float(
            jnp.max(jnp.abs(b))), name


def test_bf16_operands_stay_near_the_recurrence():
    """``operand_dtype=bfloat16`` (what the model passes on the chip): every
    matmul's operands rounded, float32 sums and state; values and gradients
    within bfloat16's rounding of the float32 recurrence."""
    args = _inputs(150, 0.99, seed=4)

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    def rounded(*a):
        return gated_delta_rule(*a, operand_dtype=jnp.bfloat16)

    with jax.default_matmul_precision("highest"):
        got, want = rounded(*args), gated_delta_recurrence(*args)
        g_got = jax.grad(scalar(rounded), argnums=(0, 1, 2))(*args)
        g_want = jax.grad(scalar(gated_delta_recurrence),
                          argnums=(0, 1, 2))(*args)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2 * float(
        jnp.max(jnp.abs(want)))
    for a, b in zip(g_got, g_want):
        assert float(jnp.linalg.norm(a - b)) < 3e-2 * float(
            jnp.linalg.norm(b))


def test_padding_rows_change_nothing_and_the_state_crosses_chunks():
    """A prefix's output does not depend on what follows it, and an early
    token still shows many chunks later when the decay is near 1 (a fault
    in the pass between chunks would lose it)."""
    q, k, v, g, beta = _inputs(200, 0.999, seed=2)
    with jax.default_matmul_precision("highest"):
        whole = gated_delta_rule(q, k, v, g, beta, chunk=16)
        prefix = gated_delta_rule(q[:, :77], k[:, :77], v[:, :77],
                                  g[:, :77], beta[:, :77], chunk=16)
        moved = gated_delta_rule(q, k, v.at[:, 0].add(1.0), g, beta,
                                 chunk=16)
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


def test_keys_that_point_alike_stay_finite_and_right():
    """What gave NaN on the chip (PERF.md section 6, PR 29): keys nearly
    parallel over a whole chunk with ``beta`` near 1 and a decay near 1."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    k = jax.random.normal(keys[0], (1, 1, 2, 16)) + 0.1 * jax.random.normal(
        keys[1], (1, 200, 2, 16))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (1, 200, 4, 16))
    g, beta = jnp.full((1, 200, 4), -0.005), jnp.full((1, 200, 4), 0.95)
    with jax.default_matmul_precision("highest"):
        got = gated_delta_rule(k * 0.25, k, v, g, beta)     # 2 key heads
        want = gated_delta_recurrence(jnp.repeat(k * 0.25, 2, axis=2),
                                      jnp.repeat(k, 2, axis=2), v, g, beta)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))
