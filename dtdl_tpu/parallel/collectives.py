"""Collectives adapter — the XLA replacement for NCCL / MPI / gRPC rings.

Inside a jitted SPMD program these helpers emit XLA collectives
(`AllReduce`, `AllGather`, `CollectivePermute`) that ride ICI within a slice
and DCN across slices, chosen by which mesh axis they name.  They replace the
reference's backend zoo: NCCL bucketed allreduce fired from ``loss.backward()``
(reference pytorch/distributed_data_parallel.py:132 via the DDP grad hooks),
ChainerMN's ``pure_nccl``/``naive`` communicators (reference
chainer/train_mnist_multi.py:49-62), and TF's collective executor driven by
TF_CONFIG (reference tensorflow2/mnist_multi_worker_strategy.py:18-27).

Host-level (outside-jit) utilities cover the reference's process-level
collectives: dataset scatter and cross-host broadcast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from dtdl_tpu.runtime.mesh import DATA_AXIS


# ---- inside-jit (SPMD) collectives -----------------------------------------

def localize(tree, axis: str = DATA_AXIS):
    """Mark a replicated pytree as per-replica varying inside shard_map.

    JAX's shard_map types values by which manual axes they vary over (VMA).
    Differentiating a per-replica loss w.r.t. *replicated* params would make
    the transpose insert an implicit psum — the gradient would arrive already
    summed and an explicit pmean would silently be an identity.  Casting
    params to 'varying' first keeps gradients per-replica so `grad_sync` below
    is a real mean-allreduce, exactly mirroring DDP's explicit bucketed
    allreduce (reference pytorch/distributed_data_parallel.py:74,132).
    """
    return jax.tree.map(
        lambda x: jax.lax.pcast(x, axis, to="varying"), tree)


def grad_sync(grads, axis: str = DATA_AXIS):
    """Mean-allreduce a gradient pytree across the data axis.

    The TPU equivalent of DDP's bucketed NCCL allreduce (reference
    pytorch/distributed_data_parallel.py:74,132) and ChainerMN's
    multi-node-optimizer allreduce (reference chainer/train_mnist_multi.py:81-83).
    XLA schedules these AllReduces inside the backward pass, each right after
    its gradient's fusion, but on a v5e as **synchronous** ``all-reduce``
    instructions (no ``-start``/``-done`` pair): the core waits out each one,
    so this is not the comm/compute overlap torch gets from grad hooks.  In
    ``olmo1b-train-ddp4`` the 27 of them stand 45 ms a step exposed (PERF.md
    section 7, which also says which compiler options changed nothing and
    what a reduce-scatter + all-gather form compiles to).
    """
    return lax.pmean(grads, axis_name=axis)


def all_reduce_sum(tree, axis: str = DATA_AXIS):
    return lax.psum(tree, axis_name=axis)


def all_reduce_mean(tree, axis: str = DATA_AXIS):
    return lax.pmean(tree, axis_name=axis)


def all_gather_batch(tree, axis: str = DATA_AXIS):
    """Gather per-replica shards into the full global batch on every replica."""
    return jax.tree.map(
        lambda x: lax.all_gather(x, axis_name=axis, axis=0, tiled=True), tree)


def broadcast_from(tree, root: int = 0, axis: str = DATA_AXIS):
    """Replicate replica ``root``'s value to all replicas on ``axis``."""
    def _bcast(x):
        masked = jnp.where(lax.axis_index(axis) == root, x, jnp.zeros_like(x))
        return lax.psum(masked, axis_name=axis)
    return jax.tree.map(_bcast, tree)


def axis_index(axis: str = DATA_AXIS):
    return lax.axis_index(axis)


def pvary_like(x, *refs):
    """Cast ``x`` to vary over every manual axis any of ``refs`` varies over.

    shard_map's VMA typing requires scan carries to enter with the same
    varying-axis set they leave with; zero-initialized accumulators start
    unvarying, so loops that mix them with sharded activations must pre-cast.
    No-op outside shard_map.
    """
    want = set()
    for r in refs:
        want |= set(getattr(jax.typeof(r), "vma", ()) or ())
    have = set(getattr(jax.typeof(x), "vma", ()) or ())
    missing = tuple(sorted(want - have))
    return lax.pcast(x, missing, to="varying") if missing else x


# ---- host-level (outside-jit) utilities ------------------------------------

def host_broadcast(tree, is_source: bool | None = None):
    """Broadcast host-side data from process 0 to all processes.

    Equivalent of ChainerMN's rank-0-loads-then-scatter pattern's broadcast
    half (reference chainer/train_mnist_multi.py:87-90).  No-op when single
    process.
    """
    if jax.process_count() == 1:
        return tree
    from jax.experimental import multihost_utils
    if is_source is None:
        is_source = jax.process_index() == 0
    return multihost_utils.broadcast_one_to_all(tree, is_source=is_source)


def assert_same_across_hosts(tree, name: str = "value") -> None:
    """Debug-mode cross-host checksum (SURVEY §5.2's race-detection stand-in)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.assert_equal(tree, fail_message=f"{name} diverged across hosts")
