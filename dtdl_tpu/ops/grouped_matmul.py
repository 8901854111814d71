"""Grouped matmul over the experts a chip holds: rows sorted by expert, each
row tile multiplied by its own expert's weight.

The expert layer (models/transformer.py:HeldExperts) sorts the assignments
it keeps into a buffer of ``R`` rows fixed by shapes, every expert's group
starting on a row tile and holding at least one tile, so a tile of
:data:`ROW_TILE` rows belongs to exactly one expert.  ``tile_expert``
[R / ROW_TILE] int32 says which, in non-decreasing order; it reaches the
kernels by scalar prefetch and picks the weight block in the index map.
Two Pallas kernels:

* ``moe_gmm``  - ``y[tile] = x[tile] @ w[tile_expert[tile]]``, and with
  ``transpose_rhs`` ``x[tile] @ w[...]^T`` (the input's gradient).  A weight
  block that the next tile shares is not fetched again, so a pass reads
  each expert's weight once.
* ``moe_tgmm`` - ``dw[e] = sum over e's tiles of x[tile]^T @ dy[tile]`` (the
  weights' gradient), accumulated in float32 in the output block while
  consecutive tiles share it.  Every expert owns a tile, so every block
  of ``dw`` is written.

**A call's cost is a function of its shapes alone**: the grid is ``R /
ROW_TILE`` steps whatever was routed, every tile of the rows it is given is
computed (the rows behind the last live one are zeros and give zeros), and
the one ``pl.when`` (zeroing ``dw[e]`` at an expert's first tile) fires once
an expert a call.  The design follows megablox
(``jax.experimental.pallas.ops.tpu.megablox``: groups by scalar prefetch, a
transposed kernel for the weights' gradient) without its dynamic grid; no
code is taken from it.  How many rows a call is given is the layer's to
say, and it has two answers, both from shapes (:func:`held_buffer_rows`,
:func:`first_buffer_rows`): the first buffer, a prefix of the full one that
holds :data:`FIRST_MULTIPLE` times the expected rows, where the routing fits
it, and the full buffer where it does not.  So a step's cost takes one of
two values a layer, never a value that follows the seed.

**The buffers' rows** and **the way in and out of them** (:func:`rows_of`,
:func:`weighted_rows_sum`) sit here too.  Both are
gathers, and so are their gradients: an assignment has at most one row and a
row at most one assignment, so the transpose of "row ``r`` reads token
``t``" is "token ``t`` sums its ``top_k`` rows", which needs no scatter
(a scatter-add of 12,800 rows of 2048 took 1.2 ms on a v5e where the gather
of as many took 0.19 ms: PERF.md section 6, PR 29).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtdl_tpu.ops import attention as _attention
from dtdl_tpu.ops.attention import _sds, _vma_of

ROW_TILE = 128      # the MXU's height; an expert's group is a multiple of it

# The full buffer holds this multiple of the assignments expected here under
# even routing, and never more than every choice of every token.  16 is
# ``router_width / held`` of the Qwen3-Next share (512 / 32), where the two
# meet: every assignment the shapes allow has a row, so no routing overflows.
# Why not less: a share's router is trained by the held experts' terms alone
# (the absent experts' are absent), and under AdamW at 3e-4 it swings towards
# them in its first twenty steps.  On a v5e at that cell's size (8,190
# tokens, top 10 of 512, 32 held, 5,119 assignments expected, no balance
# loss, nothing dropped) the most rows a layer needed over 48 steps read, a
# seed, 1.63 to 4.03 times the expected ones (12 seeds: 4.03, 3.90, 3.15,
# 3.13, 3.00, 2.90, ...; the peaks at steps 10 to 17, 1.1 to 2.5 after
# them; PERF.md section 6, PR 29).  1.25 times the largest is 5.0, which a
# log-normal through the twelve puts within reach of one run in eighty: a
# run that overflows is a failed run, so the full buffer takes what no seed
# can pass.
ROWS_MULTIPLE = 16.0

# The first buffer, the one a layer computes unless its routing does not fit
# (models/transformer.py:HeldExperts picks a layer a step, on the device),
# holds this multiple of the expected assignments.  A layer that does not
# fit drops nothing: it takes the full buffer that step, at the full
# buffer's cost.  8 is twice the largest reading of the probe above (4.03);
# the log-normal through its twelve per-seed maxima (mean of logs 0.963,
# deviation 0.289) puts a run of 48 steps past 8 once in 18,000, past 6 once
# in 490, past 5 once in 86: a benchmark cell is run some twenty-five times
# a check, and a run that meets the full buffer is a slower run, so the
# multiple is the one that almost none meets.  In that cell 352 tiles
# (45,056 rows) are computed where the full buffer has 672 (86,016).
FIRST_MULTIPLE = 8.0


def held_buffer_rows(tokens: int, top_k: int, held: int,
                     router_width: int) -> tuple[int, float]:
    """``(R, expected)``: the rows of the held experts' full buffer, from
    shapes alone, and the assignments expected here under even routing.
    ``R`` is :data:`ROWS_MULTIPLE` times the expected ones, at most every
    choice of every token, in whole row tiles, and one tile more an expert:
    a group starts on a tile and holds at least one, which costs at most
    that."""
    expected = tokens * top_k * held / router_width
    most = tokens * min(top_k, held)
    tiles = math.ceil(min(ROWS_MULTIPLE * expected, most) / ROW_TILE) + held
    return tiles * ROW_TILE, expected


def first_buffer_rows(tokens: int, top_k: int, held: int,
                      router_width: int) -> int:
    """The rows of the first buffer, from shapes alone: :data:`FIRST_MULTIPLE`
    times the expected assignments in whole row tiles and one tile more an
    expert, and never more than the full buffer's (:func:`held_buffer_rows`):
    where the two are equal there is one buffer."""
    full, expected = held_buffer_rows(tokens, top_k, held, router_width)
    tiles = math.ceil(FIRST_MULTIPLE * expected / ROW_TILE) + held
    return min(tiles * ROW_TILE, full)


def _gmm_kernel(tile_expert, x_ref, w_ref, o_ref, *, transpose_rhs):
    del tile_expert                     # read by the index maps
    contract = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    o_ref[...] = lax.dot_general(
        x_ref[...], w_ref[0], contract,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def moe_gmm(x, w, tile_expert, transpose_rhs: bool = False):
    """[R, N] = rows [R, K] times their tile's expert weight of ``w``
    [E, K, N] ([E, N, K] with ``transpose_rhs``)."""
    rows = x.shape[0]
    if rows % ROW_TILE or tile_expert.shape != (rows // ROW_TILE,):
        raise ValueError(f"{rows} rows need {rows / ROW_TILE} tile ids, "
                         f"got {tile_expert.shape}")
    return _gmm_call(x, w, tile_expert.astype(jnp.int32), transpose_rhs,
                     _attention._use_interpret())


# Under ``jax.jit`` so that a train step traces a kernel's body and lowers it
# to Mosaic once a shape and not once a call: an expert layer calls each of
# its six shapes in three passes, and both sizes of its buffer are in the
# step (PERF.md section 6, PR 32; ops/gated_delta.py does the same).
# ``interpret`` is an argument so that jit's cache tells the two lowerings of
# one shape apart.
@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def _gmm_call(x, w, tile_expert, transpose_rhs, interpret):
    rows, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, k), lambda t, te: (t, 0)),
            pl.BlockSpec((1,) + w.shape[1:], lambda t, te: (te[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, n), lambda t, te: (t, 0)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name="moe_gmm",
        grid_spec=grid_spec,
        out_shape=_sds((rows, n), x.dtype, _vma_of(x, w)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(tile_expert, x, w)


def _tgmm_kernel(tile_expert, x_ref, dy_ref, o_ref):
    t = pl.program_id(0)
    first = jnp.logical_or(
        t == 0, tile_expert[t] != tile_expert[jnp.maximum(t - 1, 0)])

    @pl.when(first)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += lax.dot_general(
        x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def moe_tgmm(x, dy, tile_expert, n_experts: int):
    """[E, K, N] float32: each expert's ``x^T @ dy`` over its own tiles.
    ``tile_expert`` must be non-decreasing and name every expert."""
    return _tgmm_call(x, dy, tile_expert.astype(jnp.int32), n_experts,
                      _attention._use_interpret())


@functools.partial(jax.jit, static_argnames=("n_experts", "interpret"))
def _tgmm_call(x, dy, tile_expert, n_experts, interpret):
    rows, k = x.shape
    n = dy.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, k), lambda t, te: (t, 0)),
            pl.BlockSpec((ROW_TILE, n), lambda t, te: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, k, n), lambda t, te: (te[t], 0, 0)),
    )
    return pl.pallas_call(
        _tgmm_kernel,
        name="moe_tgmm",
        grid_spec=grid_spec,
        out_shape=_sds((n_experts, k, n), jnp.float32, _vma_of(x, dy)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
    )(tile_expert, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def grouped_matmul(x, w, tile_expert):
    """``moe_gmm`` with its gradients: ``dx`` by ``moe_gmm`` on ``w``
    transposed, ``dw`` by ``moe_tgmm`` (cast to ``w``'s dtype)."""
    return moe_gmm(x, w, tile_expert)


def _grouped_fwd(x, w, tile_expert):
    return moe_gmm(x, w, tile_expert), (x, w, tile_expert)


def _grouped_bwd(res, dy):
    x, w, tile_expert = res
    dx = moe_gmm(dy, w, tile_expert, transpose_rhs=True)
    dw = moe_tgmm(x, dy, tile_expert, w.shape[0]).astype(w.dtype)
    return dx, dw, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def _take(x, index):
    """``x[index]`` along the first axis; an index of ``len(x)`` reads 0."""
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


def _sum_take(rows, assign_row, weight=None):
    """[T, ...] float32: each token's ``top_k`` rows of ``rows`` [R, ...]
    summed (``assign_row`` [T, top_k]; ``R`` reads 0), each times its
    ``weight`` [T, top_k] if given.  One gather a choice, so that no
    [T, top_k, ...] value is ever held."""
    total = 0.0
    for j in range(assign_row.shape[1]):
        term = _take(rows, assign_row[:, j]).astype(jnp.float32)
        if weight is not None:
            term = term * weight[:, j].reshape((-1,) + (1,) * (term.ndim - 1))
        total = total + term
    return total


@jax.custom_vjp
def rows_of(x, row_assign, assign_row):
    """The buffer's rows [R, d] of the tokens ``x`` [T, d]: row ``r`` is the
    token of assignment ``row_assign[r]`` (token ``a // top_k``; ``T *
    top_k`` marks a row no assignment has: zeros).  ``assign_row`` [T,
    top_k] is the same map from the other side (``R``: no row), which the
    gradient reads: ``dx[t]`` is the sum of the token's rows' cotangents."""
    return _take(x, row_assign // assign_row.shape[1])


def _rows_of_fwd(x, row_assign, assign_row):
    return rows_of(x, row_assign, assign_row), assign_row


def _rows_of_bwd(assign_row, d_rows):
    return _sum_take(d_rows, assign_row).astype(d_rows.dtype), None, None


rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


@jax.custom_vjp
def weighted_rows_sum(y, gates, row_assign, assign_row):
    """[T, d] float32: ``out[t] = sum_j gates[t, j] * y[assign_row[t, j]]``,
    the way back out of the buffer (``y`` [R, d], ``gates`` [T, top_k]; the
    index arrays as in :func:`rows_of`).  The gradients are gathers too:
    ``dy[r]`` is its token's cotangent times the row's gate, and a gate's is
    the product of its row with that cotangent, summed over ``d``."""
    return _sum_take(y, assign_row, gates)


def _weighted_fwd(y, gates, row_assign, assign_row):
    return (weighted_rows_sum(y, gates, row_assign, assign_row),
            (y, gates, row_assign, assign_row))


def _weighted_bwd(res, d_out):
    y, gates, row_assign, assign_row = res
    top_k = assign_row.shape[1]
    d_rows = _take(d_out.astype(y.dtype), row_assign // top_k)      # [R, d]
    row_gate = _take(gates.reshape(-1), row_assign)                 # [R]
    d_row_gate = jnp.sum(d_rows.astype(jnp.float32)
                         * y.astype(jnp.float32), axis=-1)
    dy = (d_rows.astype(jnp.float32) * row_gate[:, None]).astype(y.dtype)
    d_gates = _take(d_row_gate, assign_row.reshape(-1)).reshape(gates.shape)
    return dy, d_gates.astype(gates.dtype), None, None


weighted_rows_sum.defvjp(_weighted_fwd, _weighted_bwd)


def grouped_matmul_reference(x, w, tile_expert):
    """The same by a plain gather of weights, for tests."""
    per_row = jnp.repeat(tile_expert, ROW_TILE)
    return jnp.einsum("rk,rkn->rn", x, w[per_row],
                      preferred_element_type=jnp.float32).astype(x.dtype)
