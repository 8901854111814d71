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

**The channel-wise rule** (Kimi Delta Attention: ``S <- Diag(exp(g_t)) S``
with ``g_t`` a vector over the key channels) is the second half of this
file: :func:`kda_rule`, :func:`kda_recurrence`, kernels ``kda_chunk_fwd`` /
``kda_chunk_bwd``.  It shares :func:`_block_inverse`, the masks, the
padding and the loop with the scalar rule; what is its own is how ``A`` and
the decayed ``Q K^T`` are formed without a positive exponent (the comment
above :func:`_kda_tile`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

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
KDA_LOOP = "kda_loop"   # the same five of the channel-wise rule

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
# the channel-wise rule's kernels (kda_chunk_*): their chunk lengths, the one
# they get first, and the heads a grid step holds.  On a v5e at 2 x 4,095
# positions and 32 heads of 128 (PERF.md section 6, PR 33) the stage took
# 6.3 ms forward and 10.6 ms backward a layer at a chunk of 128, 8.0 and 12.9
# at 64; 8 heads a step gain 2 % on 4 and double the kernels' compile time
# (25 s for 13 s), 2 lose 5 %
KDA_KERNEL_CHUNKS = (128, 64)
KDA_TILES_PER_STEP = 4
KDA_SUB = 8             # rows of a diagonal sub-block (a float32 sublane tile)

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


_NN = (((1,), (0,)), ((), ()))      # a @ b
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


def stage_plan(key_dim: int, value_dim: int, chunk: int | None = None,
               channelwise: bool = False) -> tuple[str, int]:
    """``(path, chunk)`` of the chunk-local stage for these head sizes:
    ``"kernel"`` where the Pallas kernels take it, ``"jnp"`` otherwise, and
    the chunk length (the one asked for, or the path's own).  The kernels
    take head sizes that are whole lane widths, at a chunk they are written
    for, on a platform they run on (a TPU, or the CPU under the
    interpreter).  ``channelwise``: the rule whose decay is a vector over
    the key channels (:func:`kda_rule`), whose kernels have a first chunk
    of their own."""
    fits = (key_dim % LANES == 0 and value_dim % LANES == 0
            and jax.default_backend() in ("tpu", "cpu"))
    chunks = KDA_KERNEL_CHUNKS if channelwise else KERNEL_CHUNKS
    if chunk is None:
        chunk = chunks[0] if fits else JNP_CHUNK
    return ("kernel" if fits and chunk in chunks else "jnp"), chunk


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
    q, k, v, g, beta = _pad_to_chunks((q, k, v, g, beta), length, chunk)

    with jax.named_scope("gdn"):
        stage = _stage_kernel if path == "kernel" else _stage_jnp
        *xs, e_last = stage(q, k, v, g, beta, chunk, od)
        xs = checkpoint_name(tuple(xs), GDN_LOOP) + (e_last,)
        return _scan_chunks(xs, (b, h, dk, dv), od)[:, :length]


def _scan_chunks(xs, state_shape, od):
    """The loop over the chunks, shared by both rules: ``xs`` is what
    :func:`_stage_jnp` returns, ``[N, B, H, C, ...]`` each, the last the
    state's decay over a chunk (``[N, B, H, 1, 1]``, or ``[N, B, H, Dk, 1]``
    where each key channel decays at a rate of its own).  Returns ``o``
    [B, N * C, H, Dv] float32."""
    mm = functools.partial(_mm, operand_dtype=od)
    b, h, _, dv = state_shape

    def step(s, xs):
        w_c, u_c, q_c, qk_c, k_c, e_c = xs
        u_new = u_c - mm(w_c, s)
        o = mm(q_c, s) + mm(qk_c, u_new)
        s = e_c * s + mm(jnp.swapaxes(k_c, -1, -2), u_new)
        return s, o

    _, o = lax.scan(step, jnp.zeros(state_shape, _F32), xs)
    # [N, B, H, C, Dv] -> [B, L, H, Dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)
    return o.reshape(b, -1, h, dv)


def _pad_to_chunks(xs, length, chunk):
    """``xs`` ([B, L, ...] each) padded to a whole number of chunks:
    ``beta = 0``, ``g = 0`` rows change nothing."""
    pad = -length % chunk
    if not pad:
        return xs
    return tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                 for x in xs)


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


# ---------------------------------------------------------------------------
# the channel-wise rule (Kimi Delta Attention, arXiv:2510.26692)
# ---------------------------------------------------------------------------
#
# The decay is a vector over the key channels: ``S <- Diag(exp(g_t)) S`` with
# ``g_t`` [Dk].  ``A`` and the decayed ``Q K^T`` are then sums over channels
# of products with ``e^(Gamma_i[d] - Gamma_j[d])`` and no longer ``K K^T``
# times one decay matrix, and the obvious factorisation ``(K e^Gamma)(K
# e^-Gamma)^T`` overflows float32 inside a chunk at strong decays
# (``e^-Gamma`` is ``0.3^-128``).  **Every exponent taken here is a
# difference ``Gamma_i - Gamma_j`` with ``j <= i``, so none is positive**
# (bound: 0, at any decay and any chunk; what underflows is a product whose
# true value is that small):
#
# * inside a diagonal sub-block of :data:`KDA_SUB` rows, offset by offset:
#   for ``delta = 1 .. 7`` the rows shifted down by ``delta`` give ``k_(i -
#   delta)`` and ``Gamma_(i - delta)`` beside ``k_i`` and ``Gamma_i``, and the
#   entry ``(i, i - delta)`` is ``sum_d k_i k_(i-delta) e^(Gamma_i -
#   Gamma_(i-delta))``: elementwise, exact;
# * between sub-blocks, level by level as :func:`_block_inverse` joins them
#   (``b = 8, 16, ...``: two neighbouring ``b``-blocks into one of ``2b``):
#   about the first row ``m`` of the right half, a row ``i >= m`` carries
#   ``e^(Gamma_i - Gamma_m)`` and a row ``j < m`` ``e^(Gamma_m - Gamma_j)``,
#   and one whole-tile matmul of the two masked operands gives the level's
#   off-diagonal blocks.
#
# :func:`_kda_tile` is plain array code on one ``(chunk, head)`` tile.  The
# ``jax.numpy`` stage maps it over the tiles; the forward kernel calls it on
# the tiles of its block; the backward kernel differentiates it **inside the
# kernel** (``jax.vjp`` on the tile's values: the tile's forward and its
# transpose are formed in VMEM, so the stage keeps nothing but its inputs,
# as ``gdn_chunk_bwd``), with the four pieces jax cannot transpose there
# given rules of their own (:class:`_TileOps`).

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _tile_mm_vjp(a, b, od, dims):
    """:func:`_mm` on 2-D tiles whose transposes are the same: every
    matmul's operands cast to ``od``, the cotangent's too (a transposed
    ``dot_general`` would take a float32 cotangent beside a bfloat16
    operand, which Mosaic does not multiply)."""
    return _mm(a, b, od, dims)


def _tile_mm_fwd(a, b, od, dims):
    return _mm(a, b, od, dims), (a, b)


def _tile_mm_bwd(od, dims, res, ct):
    a, b = res
    if dims == _NN:
        da, db = _mm(ct, b, od, _NT), _mm(a, ct, od, _TN)
    elif dims == _NT:
        da, db = _mm(ct, b, od, _NN), _mm(ct, a, od, _TN)
    else:   # _TN
        da, db = _mm(b, ct, od, _NT), _mm(a, ct, od, _NN)
    return da.astype(a.dtype), db.astype(b.dtype)


_tile_mm_vjp.defvjp(_tile_mm_fwd, _tile_mm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tile_inverse_vjp(a, od):
    """:func:`_block_inverse` of one tile with ``da = -T^T dT T^T``."""
    return _block_inverse(a, od)


def _tile_inverse_fwd(a, od):
    t = _block_inverse(a, od)
    return t, t


def _tile_inverse_bwd(od, t, dt):
    return (-_mm(_mm(t, dt, od, _TN), t, od, _NT),)


_tile_inverse_vjp.defvjp(_tile_inverse_fwd, _tile_inverse_bwd)


def _roll_rows(x, shift, in_kernel):
    return pltpu.roll(x, shift, axis=0) if in_kernel \
        else jnp.roll(x, shift, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _roll_rows_vjp(x, shift, in_kernel):
    """``y_i = x_(i - shift)``, rows wrapping; the transpose rolls back."""
    return _roll_rows(x, shift, in_kernel)


_roll_rows_vjp.defvjp(
    lambda x, shift, in_kernel: (_roll_rows(x, shift, in_kernel), None),
    lambda shift, in_kernel, _, ct: (
        _roll_rows(ct, ct.shape[0] - shift, in_kernel),))


def _running_sum(x, in_kernel, reverse=False):
    if in_kernel:
        return _cumsum_rows(x, reverse)
    return jnp.cumsum(x[::-1], axis=0)[::-1] if reverse \
        else jnp.cumsum(x, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _running_sum_vjp(x, in_kernel):
    return _running_sum(x, in_kernel)


_running_sum_vjp.defvjp(
    lambda x, in_kernel: (_running_sum(x, in_kernel), None),
    lambda in_kernel, _, ct: (_running_sum(ct, in_kernel, reverse=True),))


def _right_first_rows(x, b):
    """[C, D]: every row of a ``2b``-block replaced by the first row of the
    block's right half (row ``m = start + b``)."""
    c, d = x.shape
    return jnp.concatenate([jnp.broadcast_to(x[m:m + 1], (2 * b, d))
                            for m in range(b, c, 2 * b)], axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _right_first_rows_vjp(x, b):
    return _right_first_rows(x, b)


def _right_first_rows_bwd(b, _, ct):
    """The transpose: row ``m`` takes the sum of its block's rows."""
    c, d = ct.shape
    at_m = lax.broadcasted_iota(jnp.int32, (2 * b, d), 0) == b
    return (jnp.concatenate([
        jnp.where(at_m, jnp.sum(ct[lo:lo + 2 * b], axis=0, keepdims=True),
                  0.0) for lo in range(0, c, 2 * b)], axis=0),)


_right_first_rows_vjp.defvjp(
    lambda x, b: (_right_first_rows(x, b), None), _right_first_rows_bwd)


class _TileOps(NamedTuple):
    """The pieces of :func:`_kda_tile` that differ between its three uses:
    ``in_kernel`` (Mosaic's sublane roll and the doubling running sum for
    ``jnp.roll`` and ``jnp.cumsum``) and ``differentiable`` (each piece
    with a transpose of its own, for ``jax.vjp`` inside the backward kernel
    and for the ``jax.numpy`` stage; the forward kernel takes the plain
    ones, whose lowering holds no custom-derivative call)."""
    in_kernel: bool
    differentiable: bool

    def mm(self, a, b, od, dims=_NN):
        return (_tile_mm_vjp if self.differentiable else _mm)(
            a, b, od, dims)

    def inverse(self, a, od):
        return (_tile_inverse_vjp if self.differentiable
                else _block_inverse)(a, od)

    def roll(self, x, shift):
        return (_roll_rows_vjp if self.differentiable else _roll_rows)(
            x, shift, self.in_kernel)

    def running_sum(self, x):
        return (_running_sum_vjp if self.differentiable
                else _running_sum)(x, self.in_kernel)

    def right_first_rows(self, x, b):
        return (_right_first_rows_vjp if self.differentiable
                else _right_first_rows)(x, b)


def _kda_tile(q, k, v, g, beta, od, ops: _TileOps):
    """One ``(chunk, head)`` tile of the channel-wise rule's chunk-local
    stage: q, k, g [C, Dk], v [C, Dv], beta [C, 1], all float32 (q, k, v as
    ``od`` rounded them) -> ``W``, ``U``, ``Q e^Gamma``, the decayed ``Q
    K^T``, ``K e^(Gamma_C - Gamma)`` in float32.  ``C`` is a power of two."""
    c = q.shape[0]
    mm = functools.partial(ops.mm, od=od)
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)

    def row_sum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    gamma = ops.running_sum(g)                          # [C, Dk], <= 0
    sub = min(KDA_SUB, c)
    # the diagonal sub-blocks, offset by offset (Q K^T has its diagonal too)
    kk = jnp.zeros((c, c), _F32)
    qk = jnp.where(rows == cols, row_sum(q * k), 0.0)
    for delta in range(1, sub):
        inside = (row & (sub - 1)) >= delta
        decayed = ops.roll(k, delta) * jnp.exp(
            jnp.where(inside, gamma - ops.roll(gamma, delta), 0.0))
        here = (rows - cols == delta) & inside
        kk = kk + jnp.where(here, row_sum(k * decayed), 0.0)
        qk = qk + jnp.where(here, row_sum(q * decayed), 0.0)
    # between sub-blocks, level by level about the right half's first row
    _, (_, *joins) = _inverse_masks(c)
    b = sub
    while b < c:
        right = (row & b) != 0
        first = ops.right_first_rows(gamma, b)
        e = jnp.exp(jnp.where(right, gamma - first, first - gamma))
        ke = k * e
        left_k = jnp.where(right, 0.0, ke)
        here = joins[b.bit_length() - 2] & (rows > cols)
        kk = kk + jnp.where(here, mm(jnp.where(right, ke, 0.0), left_k,
                                     dims=_NT), 0.0)
        qk = qk + jnp.where(here, mm(jnp.where(right, q * e, 0.0), left_k,
                                     dims=_NT), 0.0)
        b *= 2
    t = ops.inverse(beta * kk, od)
    e_gamma = jnp.exp(gamma)
    rest = jnp.exp(jnp.sum(g, axis=0, keepdims=True) - gamma)
    return (mm(t, beta * e_gamma * k), mm(t, beta * v), q * e_gamma, qk,
            k * rest)


def _kda_stage_jnp(q, k, v, g, beta, chunk, od):
    """:func:`_stage_jnp` of the channel-wise rule: :func:`_kda_tile` mapped
    over the tiles.  q, k, v [B, L, H, D], g [B, L, H, Dk], beta [B, L, H],
    ``L`` a multiple of ``chunk``."""
    b, length, h, _ = q.shape
    n = length // chunk

    def tiles(x):
        """[B, L, H, D] -> [N, B, H, C, D] float32, rounded as operands."""
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    def rounded(x):
        return x.astype(od or _F32).astype(_F32)

    tile = functools.partial(_kda_tile, od=od, ops=_TileOps(False, True))
    for _ in range(3):
        tile = jax.vmap(tile)
    xs = tile(*(tiles(rounded(x)) for x in (q, k, v)), tiles(g.astype(_F32)),
              tiles(beta.astype(_F32)[..., None]))
    return tuple(x if od is None else x.astype(od) for x in xs)


def _kda_specs(b, n, chunk, h, dk, dv):
    """``(grid, group, in_specs of q k v g beta, out_specs of the five)``:
    a grid step holds one chunk of one row for ``group`` heads."""
    group = next(n for n in range(min(h, KDA_TILES_PER_STEP), 0, -1)
                 if h % n == 0)
    flat = [pl.BlockSpec((1, chunk, group * width),
                         lambda bi, ni, ji: (bi, ni, ji))
            for width in (dk, dk, dv, dk)]
    small = pl.BlockSpec((1, 1, chunk, group),
                         lambda bi, ni, ji: (bi, ji, ni, 0))
    per_tile = [pl.BlockSpec((1, 1, group, chunk, width),
                             lambda bi, ni, ji: (ni, bi, ji, 0, 0))
                for width in (dk, dv, dk, chunk, dk)]
    return (b, n, h // group), group, flat + [small], per_tile


def _kda_tile_inputs(refs, i, beta, dk, dv):
    q_ref, k_ref, v_ref, g_ref = refs
    return (q_ref[0, :, i * dk:(i + 1) * dk].astype(_F32),
            k_ref[0, :, i * dk:(i + 1) * dk].astype(_F32),
            v_ref[0, :, i * dv:(i + 1) * dv].astype(_F32),
            g_ref[0, :, i * dk:(i + 1) * dk], beta[:, i:i + 1])


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *out_refs,
                    group, dk, dv, od):
    """One chunk of one row for ``group`` heads: q, k, g [C, group * dk], v
    [C, group * dv], beta [C, group] -> the loop's five operands a head."""
    beta = beta_ref[0, 0]
    for i in range(group):
        outs = _kda_tile(*_kda_tile_inputs((q_ref, k_ref, v_ref, g_ref), i,
                                           beta, dk, dv),
                         od, _TileOps(True, False))
        for ref, x in zip(out_refs, outs):
            ref[0, 0, i] = x.astype(ref.dtype)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                    dw_ref, du_ref, dqe_ref, dp_ref, dke_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *,
                    group, dk, dv, od):
    """The transpose of :func:`_kda_fwd_kernel` on the same block, by
    ``jax.vjp`` of :func:`_kda_tile` on the tile's values."""
    c = q_ref.shape[1]
    beta = beta_ref[0, 0]
    lane = lax.broadcasted_iota(jnp.int32, (c, group), 1)
    d_beta = jnp.zeros((c, group), _F32)
    tile = functools.partial(_kda_tile, od=od, ops=_TileOps(True, True))
    for i in range(group):
        _, pull = jax.vjp(tile, *_kda_tile_inputs(
            (q_ref, k_ref, v_ref, g_ref), i, beta, dk, dv))
        d_q, d_k, d_v, d_g, d_beta_i = pull(tuple(
            ref[0, 0, i].astype(_F32)
            for ref in (dw_ref, du_ref, dqe_ref, dp_ref, dke_ref)))
        dq_ref[0, :, i * dk:(i + 1) * dk] = d_q.astype(dq_ref.dtype)
        dk_ref[0, :, i * dk:(i + 1) * dk] = d_k.astype(dk_ref.dtype)
        dv_ref[0, :, i * dv:(i + 1) * dv] = d_v.astype(dv_ref.dtype)
        dg_ref[0, :, i * dk:(i + 1) * dk] = d_g
        d_beta = jnp.where(lane == i, d_beta_i, d_beta)
    dbeta_ref[0, 0] = d_beta


def _kda_flat_inputs(q, k, v, g, beta, groups):
    b, length = q.shape[:2]
    return tuple(x.reshape(b, length, -1) for x in (q, k, v, g)) \
        + (_by_group(beta, groups),)


@functools.partial(jax.jit, static_argnames=("chunk", "od", "interpret"))
def _kda_fwd_call(q, k, v, g, beta, chunk, od, interpret):
    """``kda_chunk_fwd`` on q, k, v [B, L, H, D], g [B, L, H, Dk] float32,
    beta [B, L, H]: the five ``[N, B, H, C, ...]`` operands of the loop."""
    b, length, h, dk = q.shape
    dv = v.shape[3]
    n = length // chunk
    grid, group, in_specs, out_specs = _kda_specs(b, n, chunk, h, dk, dv)
    vma = _vma_of(q, k, v, g, beta)
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, group=group, dk=dk, dv=dv, od=od),
        name="kda_chunk_fwd",
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=[_sds((n, b, h, chunk, width), q.dtype, vma)
                   for width in (dk, dv, dk, chunk, dk)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(*_kda_flat_inputs(q, k, v, g, beta, grid[2]))


@functools.partial(jax.jit, static_argnames=("chunk", "od", "interpret"))
def _kda_bwd_call(q, k, v, g, beta, cotangents, chunk, od, interpret):
    """``kda_chunk_bwd``: the cotangents of q, k, v, g, beta from those of
    the loop's five operands."""
    b, length, h, dk = q.shape
    dv = v.shape[3]
    n = length // chunk
    grid, group, in_specs, ct_specs = _kda_specs(b, n, chunk, h, dk, dv)
    vma = _vma_of(q, k, v, g, beta, *cotangents)
    d_q, d_k, d_v, d_g, d_beta = pl.pallas_call(
        functools.partial(_kda_bwd_kernel, group=group, dk=dk, dv=dv, od=od),
        name="kda_chunk_bwd",
        grid=grid, in_specs=in_specs + ct_specs, out_specs=in_specs,
        out_shape=[_sds((b, length, h * dk), q.dtype, vma),
                   _sds((b, length, h * dk), k.dtype, vma),
                   _sds((b, length, h * dv), v.dtype, vma),
                   _sds((b, length, h * dk), _F32, vma),
                   _sds((b, grid[2], length, group), _F32, vma)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(*_kda_flat_inputs(q, k, v, g, beta, grid[2]), *cotangents)
    return (d_q.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            d_g.reshape(g.shape), _from_groups(d_beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_loop_operands(q, k, v, g, beta, chunk, od):
    """The loop's five operands by ``kda_chunk_fwd``, with ``kda_chunk_bwd``
    for their gradient: the stage keeps its inputs and nothing else."""
    return tuple(_kda_fwd_call(q, k, v, g, beta, chunk, od,
                               _attention._use_interpret()))


def _kda_loop_operands_fwd(q, k, v, g, beta, chunk, od):
    return _kda_loop_operands(q, k, v, g, beta, chunk, od), (q, k, v, g, beta)


def _kda_loop_operands_bwd(chunk, od, inputs, cotangents):
    return _kda_bwd_call(*inputs, cotangents, chunk, od,
                         _attention._use_interpret())


_kda_loop_operands.defvjp(_kda_loop_operands_fwd, _kda_loop_operands_bwd)


def _kda_stage_kernel(q, k, v, g, beta, chunk, od):
    dtype = od or _F32
    return _kda_loop_operands(q.astype(dtype), k.astype(dtype),
                              v.astype(dtype), g.astype(_F32),
                              beta.astype(_F32), chunk, od)


def kda_rule(q, k, v, g, beta, chunk: int | None = None, operand_dtype=None):
    """``o`` [B, L, H, Dv] float32 of the delta rule with a channel-wise
    decay: per head, ``S <- Diag(exp(g_t)) S; u_t = beta_t (v_t - S^T k_t);
    S <- S + k_t u_t^T; o_t = S^T q_t``.

    ``q``, ``k`` [B, L, H, Dk] (normalised and scaled as the model wants
    them), ``v`` [B, L, H, Dv], ``g`` [B, L, H, Dk] the log of the decay a
    key channel (``<= 0``), ``beta`` [B, L, H].  With ``g`` the same in
    every channel this is :func:`gated_delta_rule`.  Chunkwise as that one,
    its stage chosen from shapes the same way (:func:`stage_plan` with
    ``channelwise``): Pallas kernels ``kda_chunk_fwd`` / ``kda_chunk_bwd``
    or the ``jax.numpy`` stage; the inverse, the masks, the padding and the
    loop are shared, and the loop's state decays a row at a rate of its
    own.  **No positive exponent is taken at any decay** (the comment above
    :func:`_kda_tile`).  What the loop reads carries :data:`KDA_LOOP`."""
    b, length, h, dk = q.shape
    dv = v.shape[-1]
    if k.shape[2] != h or v.shape[2] != h:
        raise ValueError("the channel-wise rule takes as many key heads as "
                         "value heads")
    od = operand_dtype
    path, chunk = stage_plan(dk, dv, chunk, channelwise=True)
    if chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is no power of two")
    q, k, v, g, beta = _pad_to_chunks((q, k, v, g, beta), length, chunk)
    with jax.named_scope("kda"):
        stage = _kda_stage_kernel if path == "kernel" else _kda_stage_jnp
        xs = checkpoint_name(stage(q, k, v, g, beta, chunk, od), KDA_LOOP)
        n = q.shape[1] // chunk
        last = jnp.sum(g.astype(_F32).reshape(b, n, chunk, h, dk), axis=2)
        e_last = jnp.exp(jnp.swapaxes(last, 0, 1))[..., None]
        return _scan_chunks(xs + (e_last,), (b, h, dk, dv), od)[:, :length]


def kda_recurrence(q, k, v, g, beta):
    """The same, token by token: the oracle."""
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
               for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)
