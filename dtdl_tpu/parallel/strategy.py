"""Parallelism strategies.

A `Strategy` owns the device mesh and defines three things the train-step
engine composes with:

* ``grad_sync``   — what happens to gradients before the optimizer update
* ``compile``     — how a per-replica step function becomes a global SPMD step
* ``shard_batch`` / ``replicate`` — where batches and parameters live

Mapping to the reference's strategy layer (SURVEY §2.2):

| reference                                             | here                    |
|-------------------------------------------------------|-------------------------|
| plain single-device loop (pytorch/single_gpu.py)      | `SingleDevice`          |
| nn.DataParallel / MirroredStrategy / ParallelUpdater  | `DataParallel(local_mesh())` |
| DistributedDataParallel / MultiWorkerMirroredStrategy / ChainerMN | `DataParallel(build_mesh())` over a multi-host mesh |
| (future TP/PP/SP axes)                                | `AutoSharded` with custom rules |

`DataParallel` uses `shard_map` with an explicit `lax.pmean` — the literal
SPMD restatement of DDP: every replica computes on its local shard of the
batch with per-replica BatchNorm statistics (matching DDP, which syncs grads
but not BN batches), gradients are mean-allreduced over ICI, and every replica
applies an identical update.  Running BN statistics are also pmean-synced so
the replicated train state stays bitwise identical across replicas (torch DDP
achieves the same end by broadcasting buffers from rank 0 each step).

`AutoSharded` instead gives XLA's SPMD partitioner the whole step with sharded
inputs and replicated params — the compiler inserts the AllReduces.  Under it,
BatchNorm reductions become global-batch (sync-BN semantics).  Both are
provided; `DataParallel` is the DDP-parity default.
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dtdl_tpu.runtime.mesh import DATA_AXIS, batch_sharded, build_mesh, local_mesh, replicated
from dtdl_tpu.parallel import collectives


class Strategy:
    """Base: single logical device semantics."""

    mesh: Mesh | None = None
    axis: str | None = None
    # the step function is traced with one device's shapes (one device, or
    # inside shard_map), so byte counts from shapes are one device's
    traces_one_device = True

    def localize(self, tree):
        """Hook: mark replicated values as per-replica before local compute."""
        return tree

    def grad_sync(self, grads):
        return grads

    def metric_sync(self, tree):
        return tree

    def sum_sync(self, tree):
        """Sum-allreduce (for exact count-weighted eval metrics)."""
        return tree

    def stats_sync(self, tree):
        return tree

    def fold_rank(self, key):
        """Decorrelate an rng across replicas (identity off-mesh)."""
        return key

    def compile(self, step_fn, donate_state: bool = True):
        """Jit a step ``(state, batch, ...) -> (state, metrics)``."""
        return jax.jit(step_fn, donate_argnums=(0,) if donate_state else ())

    def compile_eval(self, eval_fn):
        return jax.jit(eval_fn)

    def compile_predict(self, predict_fn):
        """Jit an inference fn ``(state, batch) -> outputs`` (batch-aligned)."""
        return jax.jit(predict_fn)

    def shard_batch(self, batch):
        return jax.device_put(batch)

    def replicate(self, tree):
        return jax.device_put(tree)

    @property
    def num_replicas(self) -> int:
        return 1

    def per_replica_batch(self, global_batch_size: int) -> int:
        """Explicit global-vs-per-replica semantics.

        The reference divides the batch by the *local* device count only
        (reference pytorch/distributed_data_parallel.py:71), which silently
        changes the global batch as nodes are added; we define --batch-size as
        GLOBAL and split by the world replica count (SURVEY §2.4).
        """
        n = self.num_replicas
        if global_batch_size % n:
            raise ValueError(
                f"global batch {global_batch_size} not divisible by "
                f"{n} replicas")
        return global_batch_size // n


class SingleDevice(Strategy):
    """One device, no collectives — reference pytorch/single_gpu.py:43-85."""


class MeshStrategy(Strategy):
    """Shared mesh-bearing behavior: batch/state placement over a mesh.

    ``axis`` may be a tuple of mesh axes for hierarchical data parallelism
    (e.g. ``('dcn', 'data')`` over a `hybrid_mesh`): the batch shards over
    all of them and gradient allreduces name them all, so XLA emits the
    in-slice ICI reduce and the cross-slice DCN reduce as one hierarchy.
    """

    def __init__(self, mesh: Mesh | None = None,
                 axis: str | tuple[str, ...] = DATA_AXIS):
        self.mesh = mesh if mesh is not None else build_mesh()
        self.axis = axis

    def shard_batch(self, batch):
        """Place a host batch as a global array sharded on the data axis.

        Single-process: device_put scatters local data across the mesh.
        Multi-process: each host contributes its local shard of the global
        batch (`make_array_from_process_local_data`) — the deterministic
        per-host sharding that replaces ``DistributedSampler`` wire-level
        scatter (reference chainer/train_mnist_multi.py:91-92).
        """
        sharding = batch_sharded(self.mesh, self.axis)
        if jax.process_count() == 1:
            return jax.device_put(batch, sharding)
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(sharding, x),
            batch)

    def replicate(self, tree):
        return jax.device_put(tree, replicated(self.mesh))

    @property
    def num_replicas(self) -> int:
        if isinstance(self.axis, tuple):
            out = 1
            for a in self.axis:
                out *= self.mesh.shape[a]
            return out
        return self.mesh.shape[self.axis]


class DataParallel(MeshStrategy):
    """shard_map data parallelism over a mesh axis (DP and DDP).

    Single-process over `local_mesh()` ≡ nn.DataParallel/MirroredStrategy;
    multi-process over `build_mesh()` ≡ DDP/MultiWorkerMirroredStrategy/
    ChainerMN — same code, the mesh just spans hosts.
    """

    def localize(self, tree):
        return collectives.localize(tree, self.axis)

    def grad_sync(self, grads):
        return collectives.grad_sync(grads, self.axis)

    def metric_sync(self, tree):
        return collectives.all_reduce_mean(tree, self.axis)

    def sum_sync(self, tree):
        return collectives.all_reduce_sum(tree, self.axis)

    def stats_sync(self, tree):
        return collectives.all_reduce_mean(tree, self.axis)

    def fold_rank(self, key):
        # each replica draws its own dropout mask, like per-rank DDP
        # workers; axis_index flattens tuple axes row-major, matching the
        # P((...)) batch-sharding order
        return jax.random.fold_in(key, jax.lax.axis_index(self.axis))

    def compile(self, step_fn, donate_state: bool = True):
        mapped = jax.shard_map(
            step_fn, mesh=self.mesh,
            in_specs=(P(), P(self.axis)),
            out_specs=(P(), P()),
        )
        return jax.jit(mapped, donate_argnums=(0,) if donate_state else ())

    def compile_eval(self, eval_fn):
        mapped = jax.shard_map(
            eval_fn, mesh=self.mesh,
            in_specs=(P(), P(self.axis)),
            out_specs=P(),
        )
        return jax.jit(mapped)

    def compile_predict(self, predict_fn):
        # outputs stay sharded on the data axis, aligned with the input batch
        mapped = jax.shard_map(
            predict_fn, mesh=self.mesh,
            in_specs=(P(), P(self.axis)),
            out_specs=P(self.axis),
        )
        return jax.jit(mapped)


class AutoSharded(MeshStrategy):
    """Compiler-partitioned strategy (pjit style).

    Params replicated, batch sharded on the data axis; XLA's SPMD partitioner
    inserts the collectives.  The mesh may carry extra axes (model, pipeline,
    sequence) — pass ``param_spec`` to shard the state for model parallelism;
    the data-parallel gradient allreduce still falls out of the partitioner
    automatically.  ``param_spec`` is either one ``PartitionSpec`` applied to
    every state leaf, or a callable ``(path, leaf) -> PartitionSpec``
    evaluated over the TrainState tree (``path`` is the jax key path; switch
    on it / the leaf's shape to shard kernels but replicate biases — the
    optimizer-state leaves mirror the param shapes, so one shape rule shards
    both consistently).
    """

    traces_one_device = False     # global shapes; the partitioner splits

    def __init__(self, mesh: Mesh | None = None, axis: str = DATA_AXIS,
                 param_spec=None):
        super().__init__(mesh, axis)
        self.param_spec = param_spec if param_spec is not None else P()

    @property
    def _per_leaf(self):
        return callable(self.param_spec) and \
            not isinstance(self.param_spec, P)

    def _state_sharding(self, like=None):
        if self._per_leaf:
            if like is None:
                raise ValueError("per-leaf param_spec needs the state tree")
            return jax.tree_util.tree_map_with_path(
                lambda path, leaf: NamedSharding(
                    self.mesh, self.param_spec(path, leaf)), like)
        return NamedSharding(self.mesh, self.param_spec)

    def compile(self, step_fn, donate_state: bool = True):
        batch_s = batch_sharded(self.mesh, self.axis)
        donate = (0,) if donate_state else ()
        if self._per_leaf:
            # The per-leaf sharding tree needs the state's structure, which
            # compile() doesn't have yet — bind it lazily from the first
            # state passed in.  in/out shardings are both EXPLICIT: with
            # out_shardings unspecified the partitioner is free to pick
            # output placements, and any divergence would compound step to
            # step (state feeds back in); pinning both sides makes the
            # placement an invariant instead of a hope.
            return _LazyPerLeafStep(self, step_fn, batch_s, donate)
        state_s = self._state_sharding()
        return jax.jit(
            step_fn,
            in_shardings=(state_s, batch_s),
            out_shardings=(state_s, NamedSharding(self.mesh, P())),
            donate_argnums=donate,
        )

    def compile_eval(self, eval_fn):
        state_s = None if self._per_leaf else self._state_sharding()
        return jax.jit(
            eval_fn,
            in_shardings=(state_s, batch_sharded(self.mesh, self.axis)),
            out_shardings=NamedSharding(self.mesh, P()),
        )

    def compile_predict(self, predict_fn):
        state_s = None if self._per_leaf else self._state_sharding()
        return jax.jit(
            predict_fn,
            in_shardings=(state_s, batch_sharded(self.mesh, self.axis)),
            out_shardings=batch_sharded(self.mesh, self.axis),
        )

    def replicate(self, tree):
        if self._per_leaf:
            # one device_put with a sharding pytree batches the transfers
            return jax.device_put(tree, self._state_sharding(like=tree))
        return jax.device_put(tree, self._state_sharding())


class _LazyPerLeafStep:
    """Jitted step whose state shardings bind on first call.

    AutoSharded(param_spec=<callable>) decides shardings per state leaf,
    but the state tree only exists after ``init_state``/``replicate`` —
    so the jit (with fully explicit in/out shardings, which is what keeps
    leaf placements stable across steps) is created on the first
    invocation and cached.  ``lower`` is forwarded for cost analysis."""

    def __init__(self, strategy: "AutoSharded", step_fn, batch_sharding,
                 donate):
        self._strategy = strategy
        self._step_fn = step_fn
        self._batch_s = batch_sharding
        self._donate = donate
        self._jit = None

    def _bind(self, state):
        state_s = self._strategy._state_sharding(like=state)
        mesh = self._strategy.mesh
        self._jit = jax.jit(
            self._step_fn,
            in_shardings=(state_s, self._batch_s),
            out_shardings=(state_s, NamedSharding(mesh, P())),
            donate_argnums=self._donate)

    def __call__(self, state, batch):
        if self._jit is None:
            self._bind(state)
        return self._jit(state, batch)

    def lower(self, state, batch):
        if self._jit is None:
            self._bind(state)
        return self._jit.lower(state, batch)


def data_parallel_local() -> DataParallel:
    """Single-process multi-device DP (nn.DataParallel equivalent)."""
    return DataParallel(local_mesh())


def distributed_data_parallel() -> DataParallel:
    """Global-mesh allreduce DP (DistributedDataParallel equivalent)."""
    return DataParallel(build_mesh())


def choose_strategy(name: str = "auto", mesh: Mesh | None = None) -> Strategy:
    """Pick a strategy the way the reference picks via script choice.

    'single' | 'dp' | 'ddp' | 'auto' (auto = ddp if >1 device else single).
    """
    if name == "auto":
        name = "ddp" if len(jax.devices()) > 1 else "single"
    if name == "single":
        return SingleDevice()
    if name == "dp":
        return DataParallel(mesh if mesh is not None else local_mesh())
    if name == "ddp":
        return DataParallel(mesh if mesh is not None else build_mesh())
    if name == "pjit":
        return AutoSharded(mesh)
    raise ValueError(f"unknown strategy {name!r}")
