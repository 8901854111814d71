"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) in chunkwise form.

Per head, with a state ``S`` of ``[key_dim, value_dim]`` that starts at 0:

    S <- exp(g_t) S;   u_t = beta_t (v_t - S^T k_t);   S <- S + k_t u_t^T;
    o_t = S^T q_t

:func:`gated_delta_recurrence` is that, token by token (the oracle of the
tests).  :func:`gated_delta_rule` computes the same in chunks of ``chunk``
positions, the paper's WY form: with ``gamma_i`` the running sum of ``g``
inside a chunk,

    A = tril_-1(beta_i (k_i . k_j) e^{gamma_i - gamma_j}),  T = (I + A)^-1,
    W = T (beta e^gamma K),  U = T (beta V),  U~ = U - W S,
    O = (Q e^gamma) S + tril(Q K^T e^{gamma_i - gamma_j}) U~,
    S <- e^{gamma_C} S + (K e^{gamma_C - gamma})^T U~

Everything that does not read ``S`` is batched over all chunks (matmuls of
``chunk x chunk`` and ``chunk x head`` tiles); a ``lax.scan`` over the
chunks carries ``S``.  Only differences ``gamma_i - gamma_j <= 0`` are ever
exponentiated.  ``T`` comes from block forward substitution written as ten
batched whole-tile matmuls for a chunk of 64 (:func:`_inv_unit_lower`), no
triangular solve; its gradient comes from ``T`` itself (``dA = -T^T dT
T^T``), and ``T`` carries the checkpoint name :data:`GDN_T` so that a
rematerialized block's plan can keep it (64 x 64 floats a chunk a head)
and not run the series again.  All of it is float32
``jax.numpy``: JAX differentiates it, ``jax.checkpoint`` may wrap it.  A
length that is no multiple of the chunk is padded here (``beta = 0``,
``g = 0`` rows change nothing).  No Pallas kernel yet: ``gdn.ms`` and
``gdn_roofline`` (benchmarks/metrics) are what one will be judged by.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name


# checkpoint names: what a rematerialized block's plan may keep of the rule
GDN_T = "gdn_t"         # ``T``, float32 chunk x chunk a chunk a value head
GDN_LOOP = "gdn_loop"   # what the loop reads of a chunk: W, U, Q e^gamma,
#                         the decayed Q K^T, K e^(gamma_C - gamma)


def _mm(a, b, operand_dtype):
    """``a @ b`` accumulated in float32; with ``operand_dtype`` the operands
    are cast to it first (what the MXU's default pass does to float32
    operands anyway, at half the bytes read)."""
    if operand_dtype is not None:
        a, b = a.astype(operand_dtype), b.astype(operand_dtype)
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _inv_unit_lower(a, operand_dtype=None):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., C, C], by
    block forward substitution written as whole-tile matmuls.

    With ``T_b`` the inverse of the ``b x b`` diagonal blocks of ``I + a``
    and ``E_b`` the part of ``a`` that joins two neighbouring blocks into
    one of ``2b``: ``T_2b = T_b - T_b E_b T_b`` (the lower-left block of a
    2 x 2 block inverse is ``-T_22 A_21 T_11``), from ``T_1 = I`` up to
    ``b = C``: ten batched matmuls for a chunk of 64.  Every intermediate
    is the true inverse of a sub-block, so nothing larger than ``T``'s own
    entries is ever formed.  (The nilpotent series ``prod (I + (-a)^(2^i))``
    costs the same and is not used: with keys that point alike and ``beta``
    near 1 its terms reach 1e17 before they cancel, and at matmul precision
    that gave a state that grew without bound: NaN on the chip, PERF.md
    section 6, PR 29.)  The gradient is taken from the inverse itself,
    ``da = -T^T dT T^T``: two matmuls."""
    n = a.shape[-1]
    rows = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    t = jnp.eye(n, dtype=a.dtype) - jnp.where(rows // 2 == cols // 2, a, 0.0)
    b = 2
    while b < n:
        joins = (rows // (2 * b) == cols // (2 * b)) & (rows // b != cols // b)
        t = t - _mm(_mm(t, jnp.where(joins, a, 0.0), operand_dtype), t,
                    operand_dtype)
        b *= 2
    return t


def _inv_fwd(a, operand_dtype):
    t = checkpoint_name(_inv_unit_lower(a, operand_dtype), GDN_T)
    return t, t


def _inv_bwd(operand_dtype, t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm(_mm(tt, dt, operand_dtype), tt, operand_dtype),)


_inv_unit_lower.defvjp(_inv_fwd, _inv_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, operand_dtype=None):
    """``o`` [B, L, H, Dv] float32 of the gated delta rule.

    ``q``, ``k`` [B, L, Hk, Dk] (already normalised and scaled as the model
    wants them), ``v`` [B, L, H, Dv], ``g`` [B, L, H] the log of the decay
    (``<= 0``), ``beta`` [B, L, H] the write strength.  ``Hk`` is ``H`` or
    divides it: key head ``i`` then serves value heads ``i * H / Hk`` onward,
    and ``K K^T`` and ``Q K^T`` are computed once a key head.

    ``operand_dtype`` (the model's compute dtype; None: float32 as given)
    is what every matmul's operands are cast to; products accumulate in
    float32, and the decay, ``A``, ``T``'s sums and the state stay float32.
    """
    b, length, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[-1]
    if h % hk:
        raise ValueError(f"{h} value heads over {hk} key heads")
    pad = -length % chunk
    n = (length + pad) // chunk
    od = operand_dtype
    mm = functools.partial(_mm, operand_dtype=od)

    def chunks(x, dtype=jnp.float32):
        """[B, L, H, ...] -> [N, B, H, C, ...], padded with 0."""
        x = x.astype(dtype)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    def gram(x, y):
        """``x y^T`` a chunk, float32."""
        if od is not None:
            x, y = x.astype(od), y.astype(od)
        return jnp.einsum("...ik,...jk->...ij", x, y,
                          preferred_element_type=jnp.float32)

    with jax.named_scope("gdn"):
        q, k, v = (chunks(x, od or jnp.float32) for x in (q, k, v))
        g, beta = chunks(g), chunks(beta)
        gamma = jnp.cumsum(g, axis=-1)                      # [N, B, H, C]
        rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        diff = gamma[..., :, None] - gamma[..., None, :]    # gamma_i - gamma_j
        decay = jnp.where(rows >= cols,
                          jnp.exp(jnp.where(rows >= cols, diff, 0.0)), 0.0)
        kk, qk = gram(k, k), gram(q, k)
        if h != hk:     # each key head's tiles to the value heads it serves
            q, k, kk, qk = (jnp.repeat(x, h // hk, axis=2)
                            for x in (q, k, kk, qk))
        a = jnp.where(rows > cols, beta[..., :, None] * kk * decay, 0.0)
        t = _inv_unit_lower(a, od)
        e_gamma = jnp.exp(gamma)[..., None]
        w = mm(t, beta[..., None] * e_gamma * k)            # [N, B, H, C, Dk]
        u = mm(t, beta[..., None] * v)                      # [N, B, H, C, Dv]
        last = gamma[..., -1:]
        # what the loop reads of a chunk, in the operands' dtype
        xs = checkpoint_name(
            tuple(x if od is None else x.astype(od) for x in (
                w, u, q * e_gamma, qk * decay,
                k * jnp.exp(last - gamma)[..., None])), GDN_LOOP) \
            + (jnp.exp(last)[..., None],)                   # [N, B, H, 1, 1]

        def step(s, xs):
            w_c, u_c, q_c, qk_c, k_c, e_c = xs
            u_new = u_c - mm(w_c, s)
            o = mm(q_c, s) + mm(qk_c, u_new)
            s = e_c * s + mm(jnp.swapaxes(k_c, -1, -2), u_new)
            return s, o

        s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
        _, o = lax.scan(step, s0, xs)
        # [N, B, H, C, Dv] -> [B, L, H, Dv]
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)
        return o.reshape(b, n * chunk, h, dv)[:, :length]


def gated_delta_recurrence(q, k, v, g, beta):
    """The same, token by token: the oracle."""
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None, None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
               for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)
