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
``attn_out``    the ``out`` projection's output              Attention
``mlp_up``      the ``wi`` and ``wg`` outputs                SwiGLU
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
stand one rung above the rest.  MoE blocks carry the attention names only.

**The budget** is computed, never set: the device's
``memory_stats()["bytes_limit"]`` (:func:`device_bytes_limit`) less
:data:`MARGIN`, less an estimate from shapes of what the step holds under
rung 0.  The step knows what lives outside the model
(:func:`step_held_bytes`: the state's leaves, gradients, the loss's chunk)
and says so through :func:`step_memory`; the model adds what it holds itself
(:func:`model_held_bytes`: logits, the blocks' inputs, one block's live set)
and plans (:func:`plan_checkpoints`).  Outside a
:func:`step_memory` context, where the device reports no limit (the CPU) or
where the traced shapes are not one device's (GSPMD), the plan is rung 0.
Everything here is a function of shapes and of the device kind's limit, so
every process of a job plans alike.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import NamedTuple, Sequence

import jax

from dtdl_tpu.ops.attention import FLASH_OUT, FLASH_QKV

ATTN_OUT = "attn_out"
MLP_UP = "mlp_up"

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


def saved_names(rung: int) -> tuple[str, ...]:
    """The checkpoint names a block at ``rung`` keeps."""
    return sum(_RUNG_NAMES[:rung + 1], ())


def policy(rung: int):
    """The ``jax.checkpoint`` policy of a block at ``rung``; None at rung 0
    (no policy: the program ``remat=True`` compiled before there was a plan)."""
    if rung == 0:
        return None
    return jax.checkpoint_policies.save_only_these_names(*saved_names(rung))


def residual_bytes(batch: int, seq: int, d_model: int, n_heads: int,
                   d_ff: int, itemsize: int) -> tuple[int, int, int]:
    """Bytes one block keeps at rungs 1, 2 and 3, each beyond the rung below:
    ``o`` + f32 log-sum-exp; ``q k v`` + the ``out`` projection's output;
    ``wi`` + ``wg`` (``d_ff`` 0 for a MoE block, which has no rung 3)."""
    t = batch * seq
    return (t * d_model * itemsize + batch * n_heads * seq * 4,
            4 * t * d_model * itemsize,
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


def step_held_bytes(state_bytes: int, param_leaf_bytes: Sequence[int],
                    grads_all_live: bool, tokens: int,
                    vocab_chunk_size: int) -> int:
    """What the train step holds outside the model under any plan: the
    state; the gradients, all of them where a collective or the guard reads
    them together (on one device XLA fuses each leaf's AdamW update into its
    weight-gradient matmul and none outlives its block, PERF.md section 5);
    and under the chunked loss (ops/cross_entropy.py) four ``[tokens,
    chunk]`` f32 tiles (logits, probabilities, one-hot, cotangent) and the
    table's f32 gradient accumulator (the largest leaf)."""
    held = state_bytes
    if grads_all_live:
        held += sum(param_leaf_bytes)
    if vocab_chunk_size:
        held += (4 * tokens * vocab_chunk_size * 4
                 + max(param_leaf_bytes, default=0))
    return held


def model_held_bytes(batch: int, seq: int, d_model: int, d_ff: int,
                     n_layers: int, vocab_size: int, param_bytes: int,
                     itemsize: int) -> int:
    """What the model's forward and backward hold under rung 0, from shapes:
    the f32 logits and their cotangent in the compute dtype (``vocab_size``
    0 where the caller takes the hidden states instead), the parameters'
    copy in the compute dtype (made in the forward pass and read again in
    the backward), every block's input, and one block's live set while it is
    recomputed and differentiated (``wi wg``, their product and the three
    cotangents; eight ``[tokens, d_model]`` values of the attention half)."""
    t = batch * seq
    return (t * vocab_size * (4 + itemsize)
            + param_bytes * itemsize // 4
            + n_layers * t * d_model * itemsize
            + t * (6 * d_ff + 8 * d_model) * itemsize)


class StepMemory(NamedTuple):
    """What a train step tells the model it traces (:func:`step_memory`)."""
    fun_name: str | None    # the step's name in the compile account
    held: int               # step_held_bytes
    limit: int | None       # the device's bytes_limit; None: not reported


class RematPlan(NamedTuple):
    """One traced step's plan, as the compile account keeps it."""
    fun_name: str | None
    rungs: tuple[int, ...]      # rung of each block, block 0 first
    kept_bytes: int             # residuals the plan keeps, by shape
    budget_bytes: int           # limit less margin less the estimate; >= 0
    estimate_bytes: int         # what the step holds under rung 0
    limit_bytes: int | None


# outside a train step: nobody to plan for, no limit known
_STEP: contextvars.ContextVar[StepMemory] = contextvars.ContextVar(
    "dtdl_tpu_step_memory", default=StepMemory(None, 0, None))


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
def step_memory(fun_name: str, held: int, limit: int | None):
    """While the body traces, a ``remat=True`` model plans against ``limit``
    with ``held`` bytes spoken for."""
    token = _STEP.set(StepMemory(fun_name, held, limit))
    try:
        yield
    finally:
        _STEP.reset(token)


def plan_checkpoints(costs: Sequence[Sequence[int]],
                     model_held: int) -> RematPlan:
    """The plan for blocks of ``costs`` in a model that holds ``model_held``
    bytes under rung 0.  Rung 0 throughout outside :func:`step_memory` or
    where the device reports no limit."""
    step = _STEP.get()
    estimate = step.held + model_held
    budget = (0 if step.limit is None
              else max(0, int(step.limit * (1 - MARGIN)) - estimate))
    rungs, kept = ladder(costs, budget)
    return RematPlan(step.fun_name, rungs, kept, budget, estimate, step.limit)
