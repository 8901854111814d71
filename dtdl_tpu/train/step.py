"""The jitted train-step engine.

One factory builds the compiled SPMD step for any (model, optimizer, strategy)
triple.  The step is the hot loop the reference hand-writes per script
(reference pytorch/distributed_data_parallel.py:118-152): forward, loss,
backward, gradient sync, optimizer update, metrics — except here the whole
thing is a single traced function: XLA fuses the elementwise work into the
matmuls and overlaps the gradient AllReduce with the remaining backward
computation, the way DDP's bucketed NCCL hooks do.

The strategy object injects the parallelism semantics (see
dtdl_tpu/parallel/strategy.py): `grad_sync` is `lax.pmean` under
`DataParallel`, identity under `SingleDevice`, and implicit-compiler-inserted
under `AutoSharded`.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from dtdl_tpu.models import remat_plan
from dtdl_tpu.ops import accuracy, softmax_cross_entropy
from dtdl_tpu.parallel.strategy import Strategy, SingleDevice
from dtdl_tpu.train.state import TrainState


def _forward(state: TrainState, params, batch, train: bool, rngs=None):
    """Run the model, handling BatchNorm mutability uniformly."""
    x = batch["image"]
    if state.batch_stats is not None:
        variables = {"params": params, "batch_stats": state.batch_stats}
        if train:
            logits, updates = state.apply_fn(
                variables, x, train=True, mutable=["batch_stats"],
                rngs=rngs)
            return logits, updates["batch_stats"]
        return state.apply_fn(variables, x, train=False), None
    logits = state.apply_fn({"params": params}, x, train=train, rngs=rngs)
    return logits, None


def _dropout_rngs(state: TrainState, strategy: Strategy, seed: int):
    """Per-step, per-replica dropout rng (flax ignores it if unused).

    Deterministic in (seed, step); `fold_rank` decorrelates replicas the way
    each DDP rank draws its own dropout mask.
    """
    key = jax.random.fold_in(jax.random.PRNGKey(seed), state.step)
    return {"dropout": strategy.fold_rank(key)}


def make_train_step(strategy: Strategy | None = None,
                    loss_fn: Callable = softmax_cross_entropy,
                    seed: int = 0, guard=None):
    """Build the compiled step ``(state, batch) -> (state, metrics)``.

    ``batch`` is a dict with ``image`` (global batch, leading dim sharded on
    the data axis by the strategy) and integer ``label``.  Metrics come back
    as globally averaged scalars (loss, accuracy) — what the reference prints
    every 20 steps (pytorch/distributed_data_parallel.py:144-148).
    ``seed`` feeds the per-step dropout rng (for models that use dropout).

    ``guard`` (a :class:`dtdl_tpu.resil.StepGuard`) folds the on-device
    anomaly check into this same program: a non-finite loss/grad-norm
    step keeps the old state (``where`` select — bitwise identical to
    unguarded when no fault fires) and the ``bad_step``/``grad_norm``
    metrics ride the async queue, zero added syncs.  The select runs on
    the metric-synced loss and post-``grad_sync`` grads so every replica
    takes the same branch.
    """
    strategy = strategy or SingleDevice()

    def train_step(state: TrainState, batch):
        rngs = _dropout_rngs(state, strategy, seed)

        def compute_loss(params):
            logits, new_stats = _forward(state, params, batch, train=True,
                                         rngs=rngs)
            with jax.named_scope("loss"):
                return loss_fn(logits, batch["label"]), (logits, new_stats)

        # Under DataParallel, localize() marks params per-replica so the
        # gradients below are local and grad_sync is a true mean-allreduce
        # (see dtdl_tpu/parallel/collectives.py:localize).
        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(strategy.localize(state.params))
        with jax.named_scope("grad_sync"):
            grads = strategy.grad_sync(grads)
        if new_stats is not None:
            new_stats = strategy.stats_sync(new_stats)
        with jax.named_scope("update"):
            new_state = state.apply_gradients(grads=grads,
                                              batch_stats=new_stats)
        metrics = strategy.metric_sync({
            "loss": loss,
            "accuracy": accuracy(logits, batch["label"]),
        })
        if guard is not None:
            with jax.named_scope("guard"):
                new_state, gm = guard.select(state, new_state,
                                             metrics["loss"], grads)
            metrics.update(gm)
        return new_state, metrics

    return strategy.compile(train_step)


def make_eval_step(strategy: Strategy | None = None,
                   loss_fn: Callable = softmax_cross_entropy):
    """Build the compiled eval step ``(state, batch) -> summed metrics``.

    Uses running BN statistics (train=False).  Returns **sums**, not means:
    ``{"loss_sum", "correct_sum", "count"}``, sum-allreduced across the mesh —
    the multi-node evaluator shape (reference chainer/train_mnist_multi.py:101-104
    allreduces eval metrics the same way).  Sum semantics make ragged tail
    batches exact: callers pad the batch to a shardable size and mark padding
    with ``batch["mask"] = 0``; masked examples contribute nothing.  Divide by
    ``count`` at the end (`dtdl_tpu.train.loop.evaluate` does this).
    """
    strategy = strategy or SingleDevice()

    def eval_step(state: TrainState, batch):
        logits, _ = _forward(state, state.params, batch, train=False)
        labels = batch["label"]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(labels.shape, jnp.float32)
        mask = mask.astype(jnp.float32)
        losses = loss_fn(logits, labels, reduction="none")
        correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
        return strategy.sum_sync({
            "loss_sum": (losses * mask).sum(),
            "correct_sum": (correct * mask).sum(),
            "count": mask.sum(),
        })

    return strategy.compile_eval(eval_step)


def make_lm_train_step(strategy: Strategy | None = None, seed: int = 0,
                       vocab_chunk_size: int = 0,
                       moe_aux_weight: float = 0.01, guard=None):
    """Compiled causal-LM step ``(state, batch) -> (state, metrics)``.

    ``batch``: {'tokens': int32 [B, S]} (optionally 'mask' f32 [B, S-1] over
    *target* positions).  Next-token cross entropy with shift; metrics are
    globally averaged {'loss', 'accuracy'} like the classifier step.

    ``vocab_chunk_size > 0`` switches the head to the vocab-chunked loss
    (dtdl_tpu/ops/cross_entropy.py:chunked_lm_loss, tiles of
    ``vocab_chunk_size`` vocab columns): the [B, S, V] logits are never materialized
    — fwd and bwd stream [tokens, chunk] tiles — so large-vocab models fit
    at long sequence.  Requires a model whose ``__call__`` accepts
    ``return_hidden=True`` (TransformerLM does) with a tied ``embed``
    parameter at the top of its param tree.

    MoE models (``n_experts > 0``) sow a Switch load-balance value per MoE
    layer under the 'aux_loss' collection; the step collects it and ADDS
    ``moe_aux_weight`` times the layer-mean to the training loss (the
    megatron path does the same — parallel/megatron.py).  Without this the
    sow is silently dropped and capacity routing collapses onto few
    experts.  Reported as the ``moe_aux_loss`` metric; 0 disables.

    A model whose expert layers hold one chip's share (``HeldExperts``)
    adds the metrics ``moe_overflow_rows`` (assignments that did not fit
    their full buffers; such a step's loss is made non-finite),
    ``moe_live_rows`` (the most buffer rows a layer needed) and
    ``moe_full_buffer_layers`` (how many of the step's expert layers did
    not fit their first buffer and computed the full one: the same numbers
    at a higher cost, so a step that reads above 0 is a slower step, not a
    wrong one).  A model with an untied head refuses ``vocab_chunk_size >
    0``.

    A model that returns ``(logits, MtpLogits)`` (a multi-token-prediction
    module: models/transformer.py) adds its weight times the module's cross
    entropy to the loss: the mean over the targets two ahead
    (``tokens[:, 2:]``, masked by ``mask[:, 1:]``), a mean of its own beside
    the next-token one.  It is reported as the metric ``mtp_loss``.

    ``guard`` folds the resil anomaly check into the program, exactly as
    in :func:`make_train_step`.
    """
    strategy = strategy or SingleDevice()

    def lm_train_step(state: TrainState, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(targets.shape, jnp.float32)
        # Global token count, so shards with sparser masks weigh less —
        # keeping the sharded loss/grads identical to single-device.  Each
        # replica's loss is scaled by num_replicas so grad_sync's *mean*
        # reconstructs the global sum/N exactly.
        total = strategy.sum_sync(mask.sum())
        scale = strategy.num_replicas / jnp.maximum(total, 1.0)

        rngs = _dropout_rngs(state, strategy, seed)

        def aux_term(variables):
            """Weighted layer-mean of the sow'd Switch balance values.

            Per-shard statistic: under DataParallel the loss mean across
            replicas makes this the mean of per-replica aux — each
            replica's router sees its own tokens, which is the standard
            per-device aux formulation."""
            leaves = jax.tree.leaves(variables.get("aux_loss", {}))
            if not leaves:      # static at trace time: model has no MoE
                return None, None
            aux = sum(leaves) / len(leaves)
            return moe_aux_weight * aux, aux

        def held_experts(loss, variables):
            """``(loss, stats)`` of a model whose expert layers hold a share
            (models/transformer.py:HeldExperts sows ``moe_stats``): the
            assignments that did not fit their buffers, summed over the
            layers, the most rows any layer's aligned groups needed, and
            the number of layers that took the full buffer in place of the
            first.  An overflow is an error, never a silent drop: it makes
            the step's loss non-finite."""
            stats = variables.get("moe_stats", {})
            if not stats:       # static at trace time: no such layer
                return loss, None
            flat = jax.tree_util.tree_flatten_with_path(stats)[0]

            def of(name):
                return [x for path, x in flat
                        if any(getattr(k, "key", None) == name
                               for k in path)]
            overflow = sum(of("overflow_rows"))
            loss = jnp.where(overflow > 0, jnp.nan, loss)
            return loss, {
                "moe_overflow_rows": overflow.astype(jnp.float32),
                "moe_live_rows": jnp.max(jnp.stack(
                    of("live_rows"))).astype(jnp.float32),
                "moe_full_buffer_layers": sum(
                    of("full_buffer")).astype(jnp.float32)}

        if vocab_chunk_size and "head" in state.params:
            raise ValueError(
                "vocab_chunk_size > 0 reads the head from params['embed'], "
                "the tied table; this model has a head table of its own "
                "(tie_embeddings=False): a chunked loss over params['head'] "
                "is missing, use vocab_chunk_size=0")
        collections = ["aux_loss", "moe_stats"]

        if vocab_chunk_size:
            from dtdl_tpu.ops.cross_entropy import chunked_lm_loss

            def compute_loss(params):
                h, muts = state.apply_fn({"params": params}, inputs,
                                         train=True, rngs=rngs,
                                         return_hidden=True,
                                         mutable=collections)
                b, s, d = h.shape
                emb = params["embed"]
                if hasattr(emb, "unbox"):   # flax logical-partitioning box
                    emb = emb.unbox()
                with jax.named_scope("loss"):
                    loss_sum, correct = chunked_lm_loss(
                        h.reshape(b * s, d), emb,
                        targets.reshape(b * s), mask.reshape(b * s),
                        vocab_chunk_size)
                    loss = loss_sum * scale
                term, aux = aux_term(muts)
                if term is not None:
                    loss = loss + term
                loss, held = held_experts(loss, muts)
                return loss, (correct * scale, aux, held, None)
        else:
            def cross_entropy_sum(logits, targets, mask):
                lse = jax.nn.logsumexp(logits, axis=-1)
                true = jnp.take_along_axis(
                    logits, targets[..., None].astype(jnp.int32), -1)[..., 0]
                return jnp.sum((lse - true) * mask)

            def compute_loss(params):
                out, muts = state.apply_fn({"params": params}, inputs,
                                           train=True, rngs=rngs,
                                           mutable=collections)
                logits, mtp = out if isinstance(out, tuple) else (out, None)
                mtp_loss = None
                with jax.named_scope("loss"):
                    logits = logits.astype(jnp.float32)
                    loss = cross_entropy_sum(logits, targets, mask) * scale
                    correct = jnp.sum(
                        (jnp.argmax(logits, -1) == targets) * mask) * scale
                if mtp is not None:
                    # the module's own scope (its flax name): a trace reads
                    # all of the module's work under one word
                    with jax.named_scope("mtp"):
                        ahead = mask[:, 1:]
                        mtp_loss = cross_entropy_sum(
                            mtp.logits, targets[:, 1:], ahead) * (
                            strategy.num_replicas / jnp.maximum(
                                strategy.sum_sync(ahead.sum()), 1.0))
                        loss = loss + mtp.weight * mtp_loss
                term, aux = aux_term(muts)
                if term is not None:
                    loss = loss + term
                loss, held = held_experts(loss, muts)
                return loss, (correct, aux, held, mtp_loss)

        # what a remat=True model may keep of its forward pass is planned
        # against what this step holds beside it as the backward pass
        # begins and, where a collective or the guard reads the gradients
        # together, as it ends (models/remat_plan.py)
        beside = remat_plan.step_held_bytes(
            remat_plan.tree_bytes(state),
            [remat_plan.tree_bytes(p) for p in jax.tree.leaves(state.params)],
            strategy.num_replicas > 1 or guard is not None,
            inputs.size, vocab_chunk_size)
        limit = (remat_plan.device_bytes_limit()
                 if strategy.traces_one_device else None)
        with remat_plan.step_memory("lm_train_step", beside.start, limit,
                                    beside.end):
            (loss, (acc, aux, held, mtp_loss)), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(strategy.localize(state.params))
        with jax.named_scope("grad_sync"):
            grads = strategy.grad_sync(grads)
        with jax.named_scope("update"):
            new_state = state.apply_gradients(grads=grads, batch_stats=None)
        metrics = {"loss": loss, "accuracy": acc}
        if aux is not None:
            metrics["moe_aux_loss"] = aux
        if held is not None:
            metrics.update(held)
        if mtp_loss is not None:
            metrics["mtp_loss"] = mtp_loss
        metrics = strategy.metric_sync(metrics)
        if guard is not None:
            with jax.named_scope("guard"):
                new_state, gm = guard.select(state, new_state,
                                             metrics["loss"], grads)
            metrics.update(gm)
        return new_state, metrics

    return strategy.compile(lm_train_step)


def make_predict_step(strategy: Strategy | None = None,
                      probabilities: bool = False):
    """Compiled inference step ``(state, batch) -> logits/probs``.

    Outputs stay aligned with the input batch (sharded on the data axis under
    mesh strategies); call ``jax.device_get`` / ``np.asarray`` to gather.
    """
    strategy = strategy or SingleDevice()

    def predict_step(state: TrainState, batch):
        logits, _ = _forward(state, state.params, batch, train=False)
        if probabilities:
            logits = jax.nn.softmax(logits, axis=-1)
        return logits

    return strategy.compile_predict(predict_step)
