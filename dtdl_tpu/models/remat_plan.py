"""What ``remat=True`` keeps: a checkpoint plan from shapes and the device's limit.

``TransformerLM(remat=True)`` wraps every block in ``jax.checkpoint``.  With
no policy that keeps a block's input alone and runs its whole forward again
in the backward pass — the least memory a block can take, and on a chip with
memory to spare a sixth of the step spent on work already done.  This module
chooses, block by block, which of the forward's outputs are kept instead.

**The names.**  The values the backward pass reads carry a
``jax.ad_checkpoint.checkpoint_name`` (an identity outside a policy, so
``remat=False``, serving and decode compile what they always did):

==============  ===========================================  =================
name            value                                        placed in
==============  ===========================================  =================
``flash_out``   flash attention's ``o`` and log-sum-exp      ops/attention.py
``flash_qkv``   ``q``, ``k``, ``v`` as the kernels take      ops/attention.py
                them (unrotated)
``attn_out``    the ``out`` projection's output, and the     Attention
                output gate's own projection's where the
                layer has one (``gate='own'``)
``mlp_up``      the ``wi`` and ``wg`` outputs                SwiGLU
``gdn_loop``    what the delta rule's loop reads of a chunk  ops/gated_delta.py
                (a linear-attention block)
``gdn_in``      the ``in_qkvz`` projection's output          GatedDeltaNet
``kda_loop``    what the channel-wise rule's loop reads      ops/gated_delta.py
``kda_in``      the ``in_q``, ``in_k``, ``in_v`` outputs     KimiDeltaAttention
``moe_plan``    the held experts' top-k and sorts            HeldExperts
``moe_out``     the held experts' output where a norm reads  Block._hybrid
                it (``post_norms``), before any rung
==============  ===========================================  =================

**The ladder.**  :data:`RUNGS` orders them by the recomputation a kept byte
removes (measured on a v5e, PERF.md section 5: flash 32 ms/GB, projections
13, MLP 11): rung 0 keeps nothing and is the program ``remat=True`` always
was; rung 1 keeps ``flash_out`` (the second ``flash_fwd`` call goes); rung 2
adds ``flash_qkv`` and ``attn_out`` (all four projections go); rung 3 adds
``mlp_up`` (``wi`` and ``wg`` go; ``silu(wg) * wi`` stays recomputed, it is
elementwise and fuses into ``wo``'s backward).  :func:`ladder` fills rung 1
on every block, then rung 2, then rung 3, block 0 first, and stops at the
first residual that does not fit the budget: so the first *k* blocks may
stand one rung above the rest.  MoE blocks carry the attention names only,
and a block with held experts ``moe_plan`` at every rung.  Where a norm
reads the held experts' output, the block keeps that output too
(``moe_out``), paid for before the ladder (:func:`plan_checkpoints`):
without it the recomputation runs the expert layer's whole forward again
for the norm's backward: 180 ms a GB kept in the Trinity cell, the most of
any name (measured on a v5e, PERF.md section 6, PR 36).
A linear-attention block (Gated DeltaNet) has two rungs of its own:
``gdn_loop`` (the rule's chunk-local stage is not run again: the
``gdn_chunk_fwd`` kernel, or the batched matmuls of the ``jax.numpy`` path),
filled in the ladder's first pass beside the other blocks' ``flash_out``,
then ``gdn_in`` (the ``in_qkvz`` projection goes).  The rule's ``T`` has no
name any more: the kernels never write it to HBM (PR 30).  A Kimi Delta
Attention block has the same two under its own names, ``kda_loop`` and
``kda_in``; a latent-attention block keeps the attention names as a full
block does, at its own widths (:func:`mla_residual_bytes`).

**The budget** is computed, never set: the device's
``memory_stats()["bytes_limit"]`` (:func:`device_bytes_limit`) less
:data:`MARGIN`, less an estimate from shapes of what the step holds under
rung 0 **when the backward pass begins**: that is the instant at which every
kept residual is live.  The step knows what lives outside the model
(:func:`step_held_bytes`: the state's leaves, the loss's chunk) and says so
through :func:`step_memory`; the model adds what it holds itself
(:func:`model_held_bytes`: the blocks' inputs, and the larger of the logits
and one block's live set) and plans (:func:`plan_checkpoints`).

**The head's instant and the blocks', not their sum.**  The logits and
their cotangent die with the head's backward, before the last block is
recomputed, so they are never live beside a block's live set: the estimate
takes the larger of the two, and the plan says which (``start_by``:
``"head"`` in the OLMo cells, ``"block"`` in the hybrid ones).  Their sum
would stand high by the smaller of the two (0.9 to 1.1 GB in the
benchmark's cells) and, at rows of 8,191 positions, leave no budget at all.

**Two instants, not one sum.**  Where a collective or the guard reads the
gradients together (DDP, ``StepGuard``) every parameter's float32 gradient
is live **when the backward pass ends**, and by then every residual, the
logits and the blocks' inputs are gone: the gradients take the place of what
the backward pass has released, not room beside it.  So the step names a
second instant (the state and all gradients; the model adds the parameters'
copy and one block's live set), which no plan changes: if it alone passes
limit less margin the plan is rung 0.  Between the two the step holds the
residuals and inputs of the blocks not yet differentiated and the gradients
of those already done; :func:`walk_bytes` computes that walk's peak from the
per-block costs, the blocks' inputs and their parameters' bytes, and
:func:`plan_checkpoints` takes residuals off the ladder's end until the
peak fits too.  A step on one device, whose gradients never outlive their
block, has the first instant alone, as it always had.

Outside a :func:`step_memory` context, where the device reports no limit
(the CPU) or where the traced shapes are not one device's (GSPMD), the plan
is rung 0.  Everything here is a function of shapes and of the device kind's
limit, so every process of a job plans alike.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import NamedTuple, Sequence

import jax

from dtdl_tpu.ops.attention import FLASH_OUT, FLASH_QKV
from dtdl_tpu.ops.gated_delta import GDN_LOOP, KDA_LOOP, stage_plan
from dtdl_tpu.ops.grouped_matmul import held_buffer_rows

ATTN_OUT = "attn_out"
MLP_UP = "mlp_up"
GDN_IN = "gdn_in"       # a linear-attention block's ``in_qkvz`` output
KDA_IN = "kda_in"       # a Kimi Delta Attention block's q, k, v projections
MOE_PLAN = "moe_plan"   # the held experts' choice and sort (a megabyte)
MOE_OUT = "moe_out"     # the held experts' output, where a norm reads it

RUNGS = ("recompute", "flash", "attn_proj", "mlp")
# the names each rung adds to those of the rung below it
_RUNG_NAMES = ((), (FLASH_OUT,), (FLASH_QKV, ATTN_OUT), (MLP_UP,))

# Share of the device's limit left unplanned: the runtime's own allocations
# (executable, batches in flight: 0.07-0.25 GB read on a v5e), fragmentation,
# and the estimate's error.  Against the v5e compiler's own totals over 16
# shapes of the two OLMo configurations the estimate reads from 0.1 GB under
# to 3.3 GB over with the dense head and 0.9 GB under with the chunked loss;
# against the chip's two readings 0.3 and 0.8 GB over (PERF.md section 6).
MARGIN = 1 / 16


# a linear-attention block's own ladder, by the recomputation a kept byte
# removes (measured on a v5e, PERF.md section 6, PR 29: 14 and 11 ms/GB)
_LINEAR_RUNG_NAMES = ((), (GDN_LOOP,), (GDN_IN,))
_KDA_RUNG_NAMES = ((), (KDA_LOOP,), (KDA_IN,))


def saved_names(rung: int, linear: bool | str = False, held: bool = False,
                moe_out: bool = False) -> tuple[str, ...]:
    """The checkpoint names a block at ``rung`` keeps.  A linear-attention
    block (``linear``: True or ``"linear"`` for Gated DeltaNet, ``"kda"``
    for Kimi Delta Attention) has two rungs of its own: what the delta
    rule's loop reads (the chunk-local stage goes), the input projections'
    output (they go); it has no third.  A block with held experts
    always keeps ``moe_plan``: the top-k and the sort of the assignments are
    a megabyte to keep and milliseconds to run again.  ``moe_out``: the
    block's held experts feed a norm (``post_norms``) and the plan holds
    their output (:func:`plan_checkpoints`), so it keeps ``moe_out`` too,
    at every rung: the norm's backward reads it, and without it the
    recomputation runs the whole expert layer's forward again for it."""
    names = (_KDA_RUNG_NAMES if linear == "kda"
             else _LINEAR_RUNG_NAMES if linear else _RUNG_NAMES)
    return (sum(names[:rung + 1], ()) + ((MOE_PLAN,) if held else ())
            + ((MOE_OUT,) if held and moe_out else ()))


def policy(rung: int, linear: bool | str = False, held: bool = False,
           moe_out: bool = False):
    """The ``jax.checkpoint`` policy of a block at ``rung``; None where it
    keeps nothing (rung 0 of a dense block: the program ``remat=True``
    compiled before there was a plan)."""
    names = saved_names(rung, linear, held, moe_out)
    if not names:
        return None
    return jax.checkpoint_policies.save_only_these_names(*names)


def gdn_residual_bytes(batch: int, seq: int, d_model: int, gdn,
                       itemsize: int) -> tuple[int, int, int]:
    """Bytes a linear-attention block (``gdn``: models/transformer.py's
    ``GdnSpec``) keeps at its rungs 1 and 2, each beyond the rung below:
    the loop's five inputs in the compute dtype, at the chunk the rule
    takes for these head sizes; the ``in_qkvz`` output.  It has no rung 3
    (0: :func:`ladder` passes it over)."""
    hk, hv, dk, dv = (gdn.key_heads, gdn.value_heads, gdn.key_dim,
                      gdn.value_dim)
    _, chunk = stage_plan(dk, dv)
    padded = -(-seq // chunk) * chunk * batch
    return (padded * hv * (3 * dk + dv + chunk) * itemsize,
            batch * seq * (2 * hk * dk + 2 * hv * dv) * itemsize,
            0)


def kda_residual_bytes(batch: int, seq: int, kda,
                       itemsize: int) -> tuple[int, int, int]:
    """:func:`gdn_residual_bytes` for a Kimi Delta Attention block (``kda``:
    models/transformer.py's ``KdaSpec``): the loop's five inputs at the
    channel-wise rule's chunk; the three projections' outputs."""
    h, d = kda.heads, kda.head_dim
    _, chunk = stage_plan(d, d, channelwise=True)
    padded = -(-seq // chunk) * chunk * batch
    return (padded * h * (4 * d + chunk) * itemsize,
            batch * seq * 3 * h * d * itemsize, 0)


def mla_residual_bytes(batch: int, seq: int, d_model: int, n_heads: int,
                       mla, itemsize: int) -> tuple[int, int, int]:
    """:func:`residual_bytes` for a latent-attention block (``mla``:
    models/transformer.py's ``MlaSpec``): ``o`` at the value head and the
    log-sum-exp; ``q``, ``k`` at the query/key head, ``v`` and the ``out``
    projection's output; no third rung."""
    t = batch * seq
    qk, v = mla.nope_dim + mla.rope_dim, mla.v_dim
    return (t * n_heads * v * itemsize + batch * n_heads * seq * 4,
            t * (n_heads * (2 * qk + v) + d_model) * itemsize, 0)


def residual_bytes(batch: int, seq: int, d_model: int, n_heads: int,
                   d_ff: int, itemsize: int,
                   attn_width: int | None = None,
                   own_gate: bool = False) -> tuple[int, int, int]:
    """Bytes one block keeps at rungs 1, 2 and 3, each beyond the rung below:
    ``o`` + f32 log-sum-exp; ``q k v`` + the ``out`` projection's output
    (+ the gate's own projection's output, ``own_gate``); ``wi`` + ``wg``
    (``d_ff`` 0 for a MoE block, which has no rung 3).
    ``attn_width`` is heads times head size where the model states a head
    size of its own (``q k v`` as the kernels take them, K/V repeated to
    the query heads); ``d_model`` otherwise.  A windowed layer keeps what a
    full one keeps: the band changes what recomputing ``flash_out`` costs
    (the band's tiles, ops/attention.py:band_tiles), not its bytes."""
    t = batch * seq
    width = d_model if attn_width is None else attn_width
    return (t * width * itemsize + batch * n_heads * seq * 4,
            (3 * width + d_model + (width if own_gate else 0)) * t * itemsize,
            2 * t * d_ff * itemsize)


def ladder(costs: Sequence[Sequence[int]], budget: int):
    """``(rung of each block, bytes kept)`` for per-block ``costs`` (the
    triples of :func:`residual_bytes`): rung by rung and block by block
    until the next residual does not fit.  Monotone in ``budget`` and never
    over it; a rung a block has no residual for (cost 0) is passed over."""
    rungs, left = [0] * len(costs), budget
    for rung in range(1, len(RUNGS)):
        for i, cost in enumerate(costs):
            step = cost[rung - 1]
            if not step:
                continue
            if step > left:
                return tuple(rungs), budget - left
            rungs[i], left = rung, left - step
    return tuple(rungs), budget - left


def tree_bytes(tree) -> int:
    """Bytes of a pytree's array leaves as traced (inside ``shard_map`` and
    on one device these are one device's)."""
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, "dtype"))


class StepHeld(NamedTuple):
    """What a train step holds outside the model (:func:`step_held_bytes`)."""
    start: int          # when the backward pass begins
    end: int | None     # when it ends; None: no gradient outlives its block


def step_held_bytes(state_bytes: int, param_leaf_bytes: Sequence[int],
                    grads_all_live: bool, tokens: int,
                    vocab_chunk_size: int) -> StepHeld:
    """What the train step holds outside the model under any plan, at the
    plan's two instants.

    ``start``, when the backward pass begins and every kept residual is
    live: the state, and under the chunked loss (ops/cross_entropy.py) four
    ``[tokens, chunk]`` f32 tiles (logits, probabilities, one-hot,
    cotangent) and the table's f32 gradient accumulator (the largest leaf).
    No block's gradient exists yet, so this is what a one-device step holds
    too.

    ``end``, when it ends, only where a collective or the guard reads the
    gradients together (``grads_all_live``): the state and every
    parameter's gradient; the loss's tiles are long gone.  None on one
    device without a guard, where XLA fuses each leaf's AdamW update into
    its weight-gradient matmul and no gradient outlives its block (PERF.md
    section 5).  Before PR 34 the gradients were added to the one sum the
    plan had, as if they were live beside the residuals they replace."""
    start = state_bytes
    if vocab_chunk_size:
        start += (4 * tokens * vocab_chunk_size * 4
                  + max(param_leaf_bytes, default=0))
    end = state_bytes + sum(param_leaf_bytes) if grads_all_live else None
    return StepHeld(start, end)


class ModelHeld(NamedTuple):
    """What the model holds under rung 0 (:func:`model_held_bytes`)."""
    start: int          # when the backward pass begins
    end: int            # when it ends: the parameters' copy, one live set
    block_input: int    # one block's input, freed as the block is done
    start_by: str = "block"     # what set ``start``: "head" or "block"


def model_held_bytes(batch: int, seq: int, d_model: int, d_ff: int,
                     n_layers: int, vocab_size: int, param_bytes: int,
                     itemsize: int,
                     block_live_bytes: int | None = None) -> ModelHeld:
    """What the model's forward and backward hold under rung 0, from shapes.
    When the backward pass begins (``start``): the parameters' copy in the
    compute dtype (made in the forward pass and read again in the backward;
    an untied head's table is a parameter like any other), every block's
    input, and the larger of two things that are never live at once: the
    f32 logits with their cotangent in the compute dtype, while the head is
    differentiated (``vocab_size`` 0 where the caller takes the hidden
    states instead), and one block's live set while it is recomputed and
    differentiated (``wi wg``, their product and the three cotangents; eight
    ``[tokens, d_model]`` values of the attention half), or
    ``block_live_bytes`` where the blocks are not the dense one
    (:func:`hybrid_block_live_bytes`, the largest block's).  The logits die
    with the head's backward, before the last block is recomputed, so the
    two are a head's instant and a block's, not one sum; ``start_by`` names
    the one that set ``start`` (``"head"`` where the logits weigh more).
    When the backward pass ends (``end``) the logits and the blocks' inputs
    are gone: the parameters' copy and the live set of the block
    differentiated last."""
    t = batch * seq
    if block_live_bytes is None:
        block_live_bytes = t * (6 * d_ff + 8 * d_model) * itemsize
    block_input = t * d_model * itemsize
    copy = param_bytes * itemsize // 4
    logits = t * vocab_size * (4 + itemsize)
    start = n_layers * block_input + copy + max(logits, block_live_bytes)
    return ModelHeld(start, copy + block_live_bytes, block_input,
                     "head" if logits > block_live_bytes else "block")


def hybrid_block_live_bytes(batch: int, seq: int, d_model: int,
                            itemsize: int, attn_width: int = 0,
                            gdn=None, held=None, d_ff: int = 0,
                            kda=None, mla=None,
                            post_norms: bool = False) -> int:
    """One hybrid block's live set (models/transformer.py:BlockSpec) while
    it is recomputed and differentiated.

    A full-attention block: the doubled q, K/V repeated to the query heads,
    the rotated copies, ``o`` and the gated ``o`` (eight ``attn_width``
    values), each with its cotangent.  A Gated DeltaNet block (``gdn``, a
    ``GdnSpec``): the ``in_qkvz`` projection, the conv's input and output,
    and the delta rule's values as
    ops/gated_delta.py holds them (q and k in float32 at the key heads; v
    and the loop's five inputs in the compute dtype; ``o`` in float32; the
    state at every chunk; and only where the shapes fall to the
    ``jax.numpy`` stage the decay, ``A`` and ``T`` in float32, ``chunk x
    chunk`` a chunk a value head: the kernels hold those in VMEM), and half
    as much again for the cotangents that are live at once.  Held experts (``held``, a
    ``HeldSpec``): the ``R``-row buffers (rows, gate, up, their product,
    the output), each with its cotangent, and the three weights' float32
    gradients, which a grouped matmul writes whole.  ``R`` is the **full**
    buffer's rows (``held_buffer_rows``), not the first buffer's that a
    layer computes where its routing fits: the full branch can run, both
    branches are in the one executable, and the compiler allocates for the
    larger.  A Kimi Delta Attention block (``kda``, a ``KdaSpec``): the
    three projections, the conv's input and output, q, k and the decay
    ``g`` in float32 a key channel, the gate, v and the loop's five inputs
    in the compute dtype, ``o`` in float32 and the state at every chunk,
    and half as much again.  A latent-attention block (``mla``: ``(heads,
    MlaSpec)``): q, the assembled k, v, ``kv_b``'s output and ``o``, each
    with its cotangent.  A dense MLP: six
    ``d_ff`` values.  Norms on the sublayers' outputs (``post_norms``): the
    two sublayers' outputs before their norms, each with its cotangent.  How
    the step's estimate stands against the v5e
    compiler's total for the Qwen3-Next cell is in PERF.md section 4."""
    t = batch * seq
    live = (8 if post_norms else 4) * t * d_model * itemsize
    if attn_width:
        live += 2 * 8 * t * attn_width * itemsize
    if gdn:
        hk, hv, dk, dv = (gdn.key_heads, gdn.value_heads, gdn.key_dim,
                          gdn.value_dim)
        conv = 2 * hk * dk + hv * dv
        path, chunk = stage_plan(dk, dv)
        states = -(-seq // chunk) * batch * hv * dk * dv * 4
        values = (t * (conv + hv * dv) * itemsize       # in_qkvz
                  + 2 * t * conv * itemsize             # the conv, in and out
                  + 2 * t * hk * dk * 4                 # q, k
                  + t * hv * (dv + 3 * dk + dv + chunk) * itemsize
                  + t * hv * dv * 4 + states)           # o, the states
        if path == "jnp":
            values += 3 * t * hv * chunk * 4            # decay, A, T
        live += values + values // 2
    if kda:
        h, d = kda.heads, kda.head_dim
        path, chunk = stage_plan(d, d, channelwise=True)
        states = -(-seq // chunk) * batch * h * d * d * 4
        values = (3 * t * h * d * itemsize              # in_q, in_k, in_v
                  + 2 * t * 3 * h * d * itemsize        # the conv, in and out
                  + 3 * t * h * d * 4                   # q, k, g
                  + t * h * d * itemsize                # the gate
                  + t * h * (5 * d + chunk) * itemsize  # v, the loop's five
                  + t * h * d * 4 + states)             # o, the states
        if path == "jnp":
            values += 3 * t * h * chunk * 4
        live += values + values // 2
    if mla:
        heads, sizes = mla
        qk = sizes.nope_dim + sizes.rope_dim
        live += 2 * t * heads * (2 * qk + sizes.nope_dim
                                 + 3 * sizes.v_dim) * itemsize
    if held:
        rows, _ = held_buffer_rows(t, held.top_k, held.held,
                                   held.router_width)
        live += 2 * rows * (2 * d_model + 3 * held.d_ff) * itemsize
        live += (3 * held.held * d_model * held.d_ff * 4
                 + 2 * t * held.router_width * 4)
        live += 2 * 3 * t * held.shared_d_ff * itemsize
    return live + 2 * 3 * t * d_ff * itemsize


class StepMemory(NamedTuple):
    """What a train step tells the model it traces (:func:`step_memory`)."""
    fun_name: str | None    # the step's name in the compile account
    held: int               # step_held_bytes().start
    limit: int | None       # the device's bytes_limit; None: not reported
    end_held: int | None = None     # step_held_bytes().end


class RematPlan(NamedTuple):
    """One traced step's plan, as the compile account keeps it."""
    fun_name: str | None
    rungs: tuple[int, ...]      # rung of each block, block 0 first
    kept_bytes: int             # residuals the plan keeps, by shape
    budget_bytes: int           # limit less margin less the estimate; >= 0
    estimate_bytes: int         # held under rung 0 as the backward begins
    limit_bytes: int | None
    # which instant set the model's part of the estimate: "head" (the
    # logits and their cotangent) or "block" (one block's live set)
    start_by: str
    # where gradients are read together (None where not): what the step
    # holds as the backward pass ends, whatever the plan; and the most it
    # holds between the two instants under this plan (walk_bytes).  A plan
    # that keeps less than the ladder buys for its budget was held to these.
    end_bytes: int | None = None
    walk_bytes: int | None = None
    # bytes of ``moe_out`` each block keeps (0: none), counted in
    # ``kept_bytes``; () where no block's held experts feed a norm
    moe_out: tuple[int, ...] = ()


# outside a train step: nobody to plan for, no limit known
_STEP: contextvars.ContextVar[StepMemory] = contextvars.ContextVar(
    "dtdl_tpu_step_memory", default=StepMemory(None, 0, None))


def traced_step_name() -> str | None:
    """The name of the train step that is tracing the model now, None
    outside one (an ``init``, an ``eval_shape``, a forward pass alone)."""
    return _STEP.get().fun_name


def device_bytes_limit() -> int | None:
    """``bytes_limit`` of this process's first device in whole 64 MiB, None
    where the backend reports none (the CPU).  Every process of a job must
    plan alike, and two hosts of one device kind were read 1,536 bytes apart
    (16,909,336,064 and 16,909,334,528 on v5e hosts of one and four chips):
    the rounding puts them on the same side of every rung."""
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return None if limit is None else limit >> 26 << 26


@contextlib.contextmanager
def step_memory(fun_name: str, held: int, limit: int | None,
                end_held: int | None = None):
    """While the body traces, a ``remat=True`` model plans against ``limit``
    with ``held`` bytes spoken for as the backward pass begins and, where
    gradients are read together, ``end_held`` as it ends."""
    token = _STEP.set(StepMemory(fun_name, held, limit, end_held))
    try:
        yield
    finally:
        _STEP.reset(token)


def walk_bytes(end: int, costs: Sequence[Sequence[int]],
               rungs: Sequence[int], block_input: int,
               block_grads: Sequence[int],
               moe_out: Sequence[int] = ()) -> int:
    """The most a step holds from the head's backward to the end of the
    backward pass, where every gradient stays live to the end (``end``:
    what it holds then).  The blocks are differentiated last to first; until
    block ``i`` is, the step holds what the block keeps (``costs[i]`` up to
    ``rungs[i]``) and its input in place of its parameters' gradient
    (``block_grads[i]``), and the ``moe_out`` it keeps at every rung
    (``moe_out[i]``, where given).  So
    before block ``k`` the step holds ``end`` plus the sum over the blocks
    before ``k`` of ``kept + input - gradient``, and the peak is ``end``
    plus the largest such prefix sum (the empty one is the end itself).
    The gradients outside the blocks (the table's, the final norm's) count
    from the head's backward on, and one block's live set throughout, as
    ``end`` has them."""
    peak = held = end
    for i, (cost, rung, grad) in enumerate(zip(costs, rungs, block_grads)):
        held += (sum(cost[:rung]) + block_input - grad
                 + (moe_out[i] if moe_out else 0))
        peak = max(peak, held)
    return peak


def plan_checkpoints(costs: Sequence[Sequence[int]], model: ModelHeld,
                     block_grads: Sequence[int],
                     moe_out: Sequence[int] = ()) -> RematPlan:
    """The plan for blocks of ``costs`` in a model that holds ``model``
    (:func:`model_held_bytes`) under rung 0 and whose blocks' parameters
    take ``block_grads`` bytes each.  Rung 0 throughout outside
    :func:`step_memory` or where the device reports no limit.

    The budget is limit less margin less what is held as the backward pass
    begins (``model.start``: the head's instant or a block's, whichever
    holds more).  ``moe_out`` gives each block's bytes of ``moe_out``
    (0 where its held experts feed no norm): the budget pays for them first,
    all of them at every rung, or for none where it cannot hold them all;
    the ladder spends what is left (a kept byte there removes more
    recomputation than on any rung: PERF.md section 6, PR 36).  Where the
    step named an end (gradients read together) and that
    alone passes limit less margin, the budget is 0; otherwise the walk
    between the two instants is **computed** (:func:`walk_bytes`; the margin
    is not asked to cover it) and, while its peak passes limit less margin,
    the residual the ladder filled last is given back, and ``moe_out`` once
    the ladder has nothing left.  That ends: each
    round keeps less, and at rung 0 a walk that still does not fit is an
    estimate over the limit, as it always was."""
    step = _STEP.get()
    estimate = step.held + model.start
    ceiling = 0 if step.limit is None else int(step.limit * (1 - MARGIN))
    budget = max(0, ceiling - estimate)
    end = walk = None
    if step.end_held is not None:
        end = step.end_held + model.end
        if end > ceiling:
            budget = 0
    moe_out = tuple(moe_out) if sum(moe_out) <= budget else ()
    rungs, kept = ladder(costs, budget - sum(moe_out))
    if end is not None:
        while (walk := walk_bytes(end, costs, rungs, model.block_input,
                                  block_grads, moe_out)) > ceiling and (
                kept or moe_out):
            if kept:
                rungs, kept = ladder(costs, kept - 1)
            else:
                moe_out = ()
    return RematPlan(step.fun_name, rungs, kept + sum(moe_out), budget,
                     estimate, step.limit, model.start_by, end, walk,
                     moe_out if any(moe_out) else ())
