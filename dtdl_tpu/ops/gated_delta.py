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

**Two stages.**  What does not read ``S`` is local to a chunk (the
*chunk-local stage*: the decay, ``A``, ``T``, ``W``, ``U`` and the four
other operands of the loop); a ``lax.scan`` over the chunks then carries
``S``.  Only differences ``gamma_i - gamma_j <= 0`` are ever exponentiated.
``T`` comes from block forward substitution written as whole-tile matmuls
(:func:`_block_inverse`: ten for a chunk of 64, twelve for 128), no
triangular solve and not the nilpotent series.  Matmul operands are cast to
``operand_dtype``; sums, decays, ``A``, ``T`` and the state are float32.

**Two implementations of the chunk-local stage, chosen from shapes**
(:func:`stage_plan`; no option selects between them):

* ``kernel``: two Pallas (Mosaic) kernels, ``gdn_chunk_fwd`` and
  ``gdn_chunk_bwd``, that hold a chunk's tiles in VMEM from q, k, v, g,
  beta to the loop's operands, so that no ``chunk x chunk`` float32 tensor
  (the decay, ``K K^T``, ``Q K^T``, ``A``, the intermediates of ``T``, ``T``
  itself) is written to or read from HBM.  A grid step holds one chunk of
  one row for a group of key heads with the value heads they serve
  (``K K^T`` and ``Q K^T`` once a key head), read straight from the
  ``[B, L, heads * head_dim]`` layout the model has them in.  The stage has
  a gradient of its own (``jax.custom_vjp``): the backward kernel runs a
  tile's forward again in VMEM and applies the closed forms (``dA = -T^T dT
  T^T`` among them), so the stage keeps nothing but its inputs.  Taken
  where the head sizes are multiples of the lane width (128), the chunk is
  one the kernels are written for (128, which they get unless the caller
  says 64), and the platform is a TPU or the CPU (the interpreter,
  ``ops/attention.py:_use_interpret``).
* ``jnp``: the same in batched ``jax.numpy``, which JAX differentiates.
  The fallback for every other shape (the tests' ``dk=16, dv=24``, the tiny
  rehearsal configuration), at a chunk of 64, and the kernels' oracle.

A length that is no multiple of the chunk is padded here (``beta = 0``,
``g = 0`` rows change nothing).  What the loop reads carries the checkpoint
name :data:`GDN_LOOP`, so a rematerialized block's plan can keep it and not
run the stage again.  The layer that calls the rule records the path its
shapes chose in the compile account (``runtime/compile_cache.py:gdn_paths``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtdl_tpu.ops import attention as _attention
from dtdl_tpu.ops.attention import _sds, _vma_of

# checkpoint name: what a rematerialized block's plan may keep of the rule
GDN_LOOP = "gdn_loop"   # what the loop reads of a chunk: W, U, Q e^gamma,
#                         the decayed Q K^T, K e^(gamma_C - gamma)

LANES = 128             # a head size the kernels take is a multiple of it
# The chunk lengths the kernels are written for, the one they get first.  On a
# v5e at 2 x 4,096 positions, 16 key and 32 value heads of 128 (PERF.md section
# 6, PR 30) a chunk of 128 took 3.7 ms forward and 5.3 ms backward a layer, a
# chunk of 64 5.5 and 8.6: twice the matmul work a token, in tiles that fill
# the MXU's 128 x 128, and half the loop's iterations.
KERNEL_CHUNKS = (128, 64)
JNP_CHUNK = 64          # the jax.numpy stage's (34.1 ms against 34.6 at 128)
# (value head, chunk) tiles a grid step holds at most: independent chains of
# dependent matmuls for the scheduler to interleave (4: 3.82 ms forward, 8:
# 3.69, 16: 3.62, same run)
TILES_PER_STEP = 8

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def _mm(a, b, operand_dtype, dims=(((1,), (0,)), ((), ()))):
    """``a @ b`` (or the contraction ``dims``) accumulated in float32; with
    ``operand_dtype`` the operands are cast to it first (what the MXU's
    default pass does to float32 operands anyway, at half the bytes read).
    Batch dimensions lead, as ``jnp.matmul`` has them."""
    if operand_dtype is not None:
        a, b = a.astype(operand_dtype), b.astype(operand_dtype)
    if a.ndim > 2:
        batch = tuple(range(a.ndim - 2))
        (ca,), (cb,) = dims[0]
        dims = (((ca + len(batch),), (cb + len(batch),)), (batch, batch))
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _inverse_masks(n: int):
    """The masks of :func:`_block_inverse` for an ``n x n`` tile: the
    diagonal, and level by level the entries it takes of ``a``: the 2 x 2
    diagonal blocks, then for ``b = 2, 4, ...`` what joins two neighbouring
    ``b``-blocks into one of ``2b``.  (Shifts, not ``//``: Mosaic lowers an
    integer division through ``sign``, milliseconds of lowering each.)"""
    rows = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = lax.broadcasted_iota(jnp.int32, (n, n), 1)

    def same_block(log2_size):
        shift = jnp.full((n, n), log2_size, jnp.int32)
        return lax.shift_right_logical(rows, shift) == \
            lax.shift_right_logical(cols, shift)

    levels = range(1, (n - 1).bit_length() + 1)
    return rows == cols, [same_block(1)] + [
        same_block(k + 1) & ~same_block(k) for k in levels[:-1]]


def _block_inverse(a, operand_dtype, masks=None):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., C, C], by
    block forward substitution written as whole-tile matmuls.

    With ``T_b`` the inverse of the ``b x b`` diagonal blocks of ``I + a``
    and ``E_b`` the part of ``a`` that joins two neighbouring blocks into
    one of ``2b``: ``T_2b = T_b - T_b E_b T_b`` (the lower-left block of a
    2 x 2 block inverse is ``-T_22 A_21 T_11``), from ``T_1 = I`` up to
    ``b = C``: ten matmuls for a chunk of 64.  Every intermediate is the
    true inverse of a sub-block, so nothing larger than ``T``'s own entries
    is ever formed.  (The nilpotent series ``prod (I + (-a)^(2^i))`` costs
    the same and is not used: with keys that point alike and ``beta`` near 1
    its terms reach 1e17 before they cancel, and at matmul precision that
    gave a state that grew without bound: NaN on the chip, PERF.md section
    6, PR 29.)  Plain array code: it runs batched under XLA and on one tile
    inside the kernels, which make the ``masks`` (:func:`_inverse_masks`)
    once for all their tiles."""
    diagonal, (pairs, *joins) = masks or _inverse_masks(a.shape[-1])
    zero = jnp.zeros((), a.dtype)
    t = jnp.where(diagonal, 1.0, zero).astype(a.dtype) \
        - jnp.where(pairs, a, zero)
    for level in joins:
        t = t - _mm(_mm(t, jnp.where(level, a, zero), operand_dtype), t,
                    operand_dtype)
    return t


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _inv_unit_lower(a, operand_dtype=None):
    """:func:`_block_inverse` with the gradient taken from the inverse
    itself, ``da = -T^T dT T^T``: two matmuls for twenty."""
    return _block_inverse(a, operand_dtype)


def _inv_fwd(a, operand_dtype):
    t = _block_inverse(a, operand_dtype)
    return t, t


def _inv_bwd(operand_dtype, t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm(_mm(tt, dt, operand_dtype), tt, operand_dtype),)


_inv_unit_lower.defvjp(_inv_fwd, _inv_bwd)


def stage_plan(key_dim: int, value_dim: int,
               chunk: int | None = None) -> tuple[str, int]:
    """``(path, chunk)`` of the chunk-local stage for these head sizes:
    ``"kernel"`` where the Pallas kernels take it, ``"jnp"`` otherwise, and
    the chunk length (the one asked for, or the path's own).  The kernels
    take head sizes that are whole lane widths, at a chunk they are written
    for, on a platform they run on (a TPU, or the CPU under the
    interpreter)."""
    fits = (key_dim % LANES == 0 and value_dim % LANES == 0
            and jax.default_backend() in ("tpu", "cpu"))
    if chunk is None:
        chunk = KERNEL_CHUNKS[0] if fits else JNP_CHUNK
    return ("kernel" if fits and chunk in KERNEL_CHUNKS else "jnp"), chunk


# ---------------------------------------------------------------------------
# the chunk-local stage in jax.numpy: the fallback and the kernels' oracle
# ---------------------------------------------------------------------------

def _stage_jnp(q, k, v, g, beta, chunk, od):
    """What the loop reads, ``[N, B, H, C, ...]`` each: ``W``, ``U``,
    ``Q e^gamma``, the decayed ``Q K^T``, ``K e^(gamma_C - gamma)`` in the
    operands' dtype, and ``e^(gamma_C)`` [N, B, H, 1, 1] float32.  The
    inputs are ``[B, L, heads, ...]`` with ``L`` a multiple of ``chunk``."""
    b, length, hk, _ = q.shape
    h = v.shape[2]
    n = length // chunk

    def chunks(x, dtype=_F32):
        """[B, L, H, ...] -> [N, B, H, C, ...]."""
        x = x.astype(dtype).reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    mm = functools.partial(_mm, operand_dtype=od)
    q, k, v = (chunks(x, od or _F32) for x in (q, k, v))
    g, beta = chunks(g), chunks(beta)
    gamma = jnp.cumsum(g, axis=-1)                      # [N, B, H, C]
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = gamma[..., :, None] - gamma[..., None, :]    # gamma_i - gamma_j
    decay = jnp.where(rows >= cols,
                      jnp.exp(jnp.where(rows >= cols, diff, 0.0)), 0.0)
    kk, qk = mm(k, k, dims=_NT), mm(q, k, dims=_NT)
    if h != hk:     # each key head's tiles to the value heads it serves
        q, k, kk, qk = (jnp.repeat(x, h // hk, axis=2)
                        for x in (q, k, kk, qk))
    a = jnp.where(rows > cols, beta[..., :, None] * kk * decay, 0.0)
    t = _inv_unit_lower(a, od)
    e_gamma = jnp.exp(gamma)[..., None]
    w = mm(t, beta[..., None] * e_gamma * k)            # [N, B, H, C, Dk]
    u = mm(t, beta[..., None] * v)                      # [N, B, H, C, Dv]
    last = gamma[..., -1:]
    xs = tuple(x if od is None else x.astype(od) for x in (
        w, u, q * e_gamma, qk * decay,
        k * jnp.exp(last - gamma)[..., None]))
    return xs + (jnp.exp(last)[..., None],)


# ---------------------------------------------------------------------------
# the chunk-local stage as Pallas kernels
# ---------------------------------------------------------------------------

def _cumsum_rows(x, reverse=False):
    """Running sum of ``x`` [C, n] down its rows (up them with ``reverse``)
    by doubling: ``log2 C`` shifted adds on the sublanes, float32."""
    c = x.shape[0]
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    shift = 1
    while shift < c:
        if reverse:
            moved = jnp.where(row < c - shift,
                              pltpu.roll(x, c - shift, axis=0), 0.0)
        else:
            moved = jnp.where(row >= shift, pltpu.roll(x, shift, axis=0), 0.0)
        x = x + moved
        shift *= 2
    return x


def _tile_decay(g_col, rows, cols):
    """The masked decay ``[i >= j] e^(gamma_i - gamma_j)`` [C, C] of one
    tile from its ``g`` [C, 1].  The difference is summed directly from the
    ``g`` between the two positions (``L (g_k [k > j])`` with ``L`` the lower
    triangle of ones: one float32 matmul, 0 above the diagonal), never
    positive."""
    lower = jnp.where(rows >= cols, 1.0, 0.0).astype(_F32)
    between = jnp.where(rows > cols, g_col, 0.0)
    diff = lax.dot_general(lower, between, (((1,), (0,)), ((), ())),
                           precision=_HIGHEST, preferred_element_type=_F32)
    return jnp.where(rows >= cols, jnp.exp(diff), 0.0)


def _block_values(g_ref, beta_ref, c):
    """What all tiles of a grid step share: the ``[C, C]`` index planes, the
    inverse's masks, and for the heads of the block ``g``, ``beta``,
    ``e^gamma`` and ``e^(gamma_C - gamma)``, ``[C, heads here]`` each."""
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    g, beta = g_ref[0, 0], beta_ref[0, 0]
    gamma = _cumsum_rows(g)
    return (rows, cols, _inverse_masks(c), g, beta, jnp.exp(gamma),
            jnp.exp(gamma[c - 1:c, :] - gamma))


def _key_head(q_ref, k_ref, i, dk, od):
    """Key head ``i`` of the block: ``q``, ``k`` [C, dk] in float32 (as the
    operands' dtype rounded them) and ``K K^T``, ``Q K^T``, once for the
    value heads it serves."""
    q = q_ref[0, :, i * dk:(i + 1) * dk]
    k = k_ref[0, :, i * dk:(i + 1) * dk]
    return (q.astype(_F32), k.astype(_F32), _mm(k, k, od, dims=_NT),
            _mm(q, k, od, dims=_NT))


def _tile_inverse(g_col, beta_col, kk, rows, cols, masks, od):
    """``(decay, T)`` of one tile."""
    decay = _tile_decay(g_col, rows, cols)
    a = jnp.where(rows > cols, beta_col * kk * decay, 0.0)
    return decay, _block_inverse(a, od, masks)


def _chunk_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                      w_ref, u_ref, qe_ref, p_ref, ke_ref, *,
                      group, ratio, dk, dv, od):
    """One chunk of one row for ``group`` key heads and the ``ratio`` value
    heads each serves: from q, k [C, group * dk], v [C, group * ratio * dv],
    g, beta [C, group * ratio] to the loop's five operands a value head."""
    mm = functools.partial(_mm, operand_dtype=od)
    rows, cols, masks, g, beta, e_gamma, e_rest = _block_values(
        g_ref, beta_ref, q_ref.shape[1])
    for i in range(group):
        q, k, kk, qk = _key_head(q_ref, k_ref, i, dk, od)
        for j in range(ratio):
            h = i * ratio + j
            v = v_ref[0, :, h * dv:(h + 1) * dv].astype(_F32)
            beta_h, e_gamma_h = beta[:, h:h + 1], e_gamma[:, h:h + 1]
            decay, t = _tile_inverse(g[:, h:h + 1], beta_h, kk, rows, cols,
                                     masks, od)
            w_ref[0, 0, h] = mm(t, beta_h * e_gamma_h * k).astype(w_ref.dtype)
            u_ref[0, 0, h] = mm(t, beta_h * v).astype(u_ref.dtype)
            qe_ref[0, 0, h] = (q * e_gamma_h).astype(qe_ref.dtype)
            p_ref[0, 0, h] = (qk * decay).astype(p_ref.dtype)
            ke_ref[0, 0, h] = (k * e_rest[:, h:h + 1]).astype(ke_ref.dtype)


def _head_group(hk: int, ratio: int) -> int:
    """Key heads a grid step holds: the most that divide ``hk`` and keep
    the step's tiles at :data:`TILES_PER_STEP` or fewer (at least one)."""
    group = max(1, min(hk, TILES_PER_STEP // ratio))
    while hk % group:
        group -= 1
    return group


def _by_group(x, groups):
    """[B, L, H] -> [B, groups, L, H / groups]: a group's heads side by
    side, so that a block of it is whole in its last dimension."""
    b, length, h = x.shape
    return jnp.swapaxes(x.reshape(b, length, groups, h // groups), 1, 2)


def _from_groups(x):
    """The inverse of :func:`_by_group`."""
    b, groups, length, per = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b, length, groups * per)


def _stage_specs(b, n, chunk, hk, h, dk, dv):
    """``(grid, group, in_specs of q k v g beta, out_specs of the five)``."""
    ratio = h // hk
    group = _head_group(hk, ratio)
    tiles = group * ratio
    flat = [pl.BlockSpec((1, chunk, width), lambda bi, ni, ji: (bi, ni, ji))
            for width in (group * dk, group * dk, tiles * dv)]
    small = pl.BlockSpec((1, 1, chunk, tiles),
                         lambda bi, ni, ji: (bi, ji, ni, 0))
    per_tile = [pl.BlockSpec((1, 1, tiles, chunk, width),
                             lambda bi, ni, ji: (ni, bi, ji, 0, 0))
                for width in (dk, dv, dk, chunk, dk)]
    return (b, n, hk // group), group, flat + [small, small], per_tile


def _flat_inputs(q, k, v, g, beta, groups):
    """The five inputs as the kernels' blocks take them: heads and head
    size merged (a free reshape of the model's layout), ``g`` and ``beta``
    by group."""
    b, length = q.shape[:2]
    return (q.reshape(b, length, -1), k.reshape(b, length, -1),
            v.reshape(b, length, -1), _by_group(g, groups),
            _by_group(beta, groups))


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 << 20)


# Both calls are jitted on their own: a model's linear layers share their
# shapes, and a rematerialized block under a gradient meets the forward call
# three times, so the kernels' bodies (a thousand equations each) are traced
# and lowered once a step's trace and not nine times (PERF.md section 6, PR
# 30: 31 s of a cell's set-up before this and the shifts of _inverse_masks).
# ``interpret`` is an argument so that jit's cache tells the two lowerings of
# one shape apart (a test, or tools/topology_compile.py, may ask for both).
@functools.partial(jax.jit, static_argnames=("chunk", "od", "interpret"))
def _stage_fwd_call(q, k, v, g, beta, chunk, od, interpret):
    """``gdn_chunk_fwd`` on q, k [B, L, Hk, Dk], v [B, L, H, Dv], g, beta
    [B, L, H] (``L`` a multiple of ``chunk``; q, k, v in the operands'
    dtype): the five ``[N, B, H, C, ...]`` operands of the loop."""
    b, length, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[3]
    n = length // chunk
    grid, group, in_specs, out_specs = _stage_specs(b, n, chunk, hk, h, dk, dv)
    vma = _vma_of(q, k, v, g, beta)
    return pl.pallas_call(
        functools.partial(_chunk_fwd_kernel, group=group, ratio=h // hk,
                          dk=dk, dv=dv, od=od),
        name="gdn_chunk_fwd",
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=[_sds((n, b, h, chunk, width), q.dtype, vma)
                   for width in (dk, dv, dk, chunk, dk)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(*_flat_inputs(q, k, v, g, beta, grid[2]))


def _chunk_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                      dw_ref, du_ref, dqe_ref, dp_ref, dke_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *,
                      group, ratio, dk, dv, od):
    """The transpose of :func:`_chunk_fwd_kernel` on the same block: a
    tile's decay, ``A`` and ``T`` are formed again in VMEM, and the
    cotangents of the loop's five operands become those of q, k, v, g, beta
    by the closed forms (``dT = dW Kb^T + dU Vb^T``, ``dA = -T^T dT T^T``,
    ``d diff = (dA beta K K^T + dP Q K^T) decay``, and the running sums'
    transposes).  q's and k's are summed over the value heads a key head
    serves before they are rounded once."""
    c = q_ref.shape[1]
    tiles = group * ratio
    mm = functools.partial(_mm, operand_dtype=od)
    rows, cols, masks, g, beta, e_gamma, e_rest = _block_values(
        g_ref, beta_ref, c)
    lane = lax.broadcasted_iota(jnp.int32, (c, tiles), 1)
    last_row = lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    upper = jnp.where(rows <= cols, 1.0, 0.0).astype(_F32)

    def row_sum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    d_g = d_beta = d_gamma = jnp.zeros((c, tiles), _F32)
    for i in range(group):
        q, k, kk, qk = _key_head(q_ref, k_ref, i, dk, od)
        d_kk = d_qk = jnp.zeros((c, c), _F32)
        d_q = d_k = jnp.zeros((c, dk), _F32)
        for j in range(ratio):
            h = i * ratio + j
            v = v_ref[0, :, h * dv:(h + 1) * dv].astype(_F32)
            beta_h, e_gamma_h = beta[:, h:h + 1], e_gamma[:, h:h + 1]
            e_rest_h = e_rest[:, h:h + 1]
            decay, t = _tile_inverse(g[:, h:h + 1], beta_h, kk, rows, cols,
                                     masks, od)
            d_w, d_u = dw_ref[0, 0, h], du_ref[0, 0, h]
            # W = T (beta e^gamma K), U = T (beta V)
            d_t = mm(d_w, beta_h * e_gamma_h * k, dims=_NT) \
                + mm(d_u, beta_h * v, dims=_NT)
            d_kb, d_vb = mm(t, d_w, dims=_TN), mm(t, d_u, dims=_TN)
            d_a = jnp.where(rows > cols,
                            -mm(mm(t, d_t, dims=_TN), t, dims=_NT), 0.0)
            # A = beta_i K K^T decay below the diagonal, P = Q K^T decay
            d_p = dp_ref[0, 0, h].astype(_F32)
            d_kk = d_kk + d_a * beta_h * decay
            d_qk = d_qk + d_p * decay
            d_diff = (d_a * beta_h * kk + d_p * qk) * decay
            d_between = lax.dot_general(
                upper, d_diff, (((1,), (0,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=_F32)
            d_g_h = row_sum(jnp.where(rows > cols, d_between, 0.0))
            s_kb = row_sum(d_kb * k)
            d_beta_h = row_sum(d_a * kk * decay) + s_kb * e_gamma_h \
                + row_sum(d_vb * v)
            d_qe = dqe_ref[0, 0, h].astype(_F32)
            d_ke = dke_ref[0, 0, h].astype(_F32)
            # e^gamma in W and Q e^gamma; e^(gamma_C - gamma) in K e^(...)
            d_rest = e_rest_h * row_sum(d_ke * k)
            d_gamma_h = e_gamma_h * (s_kb * beta_h + row_sum(d_qe * q)) \
                - d_rest + jnp.where(
                    last_row, jnp.sum(d_rest, axis=0, keepdims=True), 0.0)
            d_k = d_k + beta_h * e_gamma_h * d_kb + e_rest_h * d_ke
            d_q = d_q + e_gamma_h * d_qe
            dv_ref[0, :, h * dv:(h + 1) * dv] = \
                (beta_h * d_vb).astype(dv_ref.dtype)
            d_g = jnp.where(lane == h, d_g_h, d_g)
            d_beta = jnp.where(lane == h, d_beta_h, d_beta)
            d_gamma = jnp.where(lane == h, d_gamma_h, d_gamma)
        # K K^T and Q K^T, once a key head
        d_k = d_k + mm(d_kk, k) + mm(d_kk, k, dims=_TN) \
            + mm(d_qk, q, dims=_TN)
        d_q = d_q + mm(d_qk, k)
        dq_ref[0, :, i * dk:(i + 1) * dk] = d_q.astype(dq_ref.dtype)
        dk_ref[0, :, i * dk:(i + 1) * dk] = d_k.astype(dk_ref.dtype)
    dg_ref[0, 0] = d_g + _cumsum_rows(d_gamma, reverse=True)
    dbeta_ref[0, 0] = d_beta


@functools.partial(jax.jit, static_argnames=("chunk", "od", "interpret"))
def _stage_bwd_call(q, k, v, g, beta, cotangents, chunk, od, interpret):
    """``gdn_chunk_bwd``: the cotangents of q, k, v, g, beta from those of
    the loop's five operands (``[N, B, H, C, ...]``, as
    :func:`_stage_fwd_call` gives them)."""
    b, length, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[3]
    n = length // chunk
    grid, group, in_specs, ct_specs = _stage_specs(b, n, chunk, hk, h, dk, dv)
    vma = _vma_of(q, k, v, g, beta, *cotangents)
    flat, small = in_specs[:3], in_specs[3]
    d_q, d_k, d_v, d_g, d_beta = pl.pallas_call(
        functools.partial(_chunk_bwd_kernel, group=group, ratio=h // hk,
                          dk=dk, dv=dv, od=od),
        name="gdn_chunk_bwd",
        grid=grid, in_specs=in_specs + ct_specs,
        out_specs=flat + [small, small],
        out_shape=[_sds((b, length, hk * dk), q.dtype, vma),
                   _sds((b, length, hk * dk), k.dtype, vma),
                   _sds((b, length, h * dv), v.dtype, vma),
                   _sds((b, grid[2], length, h // grid[2]), _F32, vma),
                   _sds((b, grid[2], length, h // grid[2]), _F32, vma)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(*_flat_inputs(q, k, v, g, beta, grid[2]), *cotangents)
    return (d_q.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            _from_groups(d_g), _from_groups(d_beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _loop_operands(q, k, v, g, beta, chunk, od):
    """The loop's five operands by ``gdn_chunk_fwd``, with
    ``gdn_chunk_bwd`` for their gradient: the stage keeps its inputs and
    nothing else."""
    return tuple(_stage_fwd_call(q, k, v, g, beta, chunk, od,
                                 _attention._use_interpret()))


def _loop_operands_fwd(q, k, v, g, beta, chunk, od):
    return _loop_operands(q, k, v, g, beta, chunk, od), (q, k, v, g, beta)


def _loop_operands_bwd(chunk, od, inputs, cotangents):
    return _stage_bwd_call(*inputs, cotangents, chunk, od,
                           _attention._use_interpret())


_loop_operands.defvjp(_loop_operands_fwd, _loop_operands_bwd)


def _stage_kernel(q, k, v, g, beta, chunk, od):
    """The kernels' side of :func:`_stage_jnp`: the same six values."""
    b, length, _, _ = q.shape
    h = v.shape[2]
    n = length // chunk
    dtype = od or _F32
    g = g.astype(_F32)
    xs = _loop_operands(q.astype(dtype), k.astype(dtype), v.astype(dtype),
                        g, beta.astype(_F32), chunk, od)
    last = jnp.sum(g.reshape(b, n, chunk, h), axis=2)
    e_last = jnp.exp(jnp.swapaxes(last, 0, 1))[..., None, None]
    return xs + (e_last,)


# ---------------------------------------------------------------------------
# the operator
# ---------------------------------------------------------------------------

def gated_delta_rule(q, k, v, g, beta, chunk: int | None = None,
                     operand_dtype=None):
    """``o`` [B, L, H, Dv] float32 of the gated delta rule.

    ``q``, ``k`` [B, L, Hk, Dk] (already normalised and scaled as the model
    wants them), ``v`` [B, L, H, Dv], ``g`` [B, L, H] the log of the decay
    (``<= 0``), ``beta`` [B, L, H] the write strength.  ``Hk`` is ``H`` or
    divides it: key head ``i`` then serves value heads ``i * H / Hk`` onward,
    and ``K K^T`` and ``Q K^T`` are computed once a key head.

    ``operand_dtype`` (the model's compute dtype; None: float32 as given)
    is what every matmul's operands are cast to; products accumulate in
    float32, and the decay, ``A``, ``T``'s sums and the state stay float32.
    ``chunk`` is an internal of the operator (None: chosen with the path,
    :func:`stage_plan`); the tests set it to reach the padding and the passes
    between chunks at small sizes.
    """
    b, length, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[-1]
    if h % hk:
        raise ValueError(f"{h} value heads over {hk} key heads")
    od = operand_dtype
    path, chunk = stage_plan(dk, dv, chunk)
    pad = -length % chunk
    n = (length + pad) // chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    mm = functools.partial(_mm, operand_dtype=od)

    with jax.named_scope("gdn"):
        stage = _stage_kernel if path == "kernel" else _stage_jnp
        *xs, e_last = stage(q, k, v, g, beta, chunk, od)
        xs = checkpoint_name(tuple(xs), GDN_LOOP) + (e_last,)

        def step(s, xs):
            w_c, u_c, q_c, qk_c, k_c, e_c = xs
            u_new = u_c - mm(w_c, s)
            o = mm(q_c, s) + mm(qk_c, u_new)
            s = e_c * s + mm(jnp.swapaxes(k_c, -1, -2), u_new)
            return s, o

        s0 = jnp.zeros((b, h, dk, dv), _F32)
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
