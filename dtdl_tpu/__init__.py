"""dtdl_tpu — a TPU-native distributed-training framework.

A ground-up JAX/XLA rebuild of the capabilities of
MyXiaoPao/distributed-training-dl (the reference collection of per-framework
distributed-training examples): single-device training, single-process
multi-device data parallelism, multi-process/multi-host allreduce data
parallelism, dataset sharding/scatter, checkpoint/resume, metric logging, and
per-example CLIs — expressed as SPMD programs over a `jax.sharding.Mesh`, with
gradient synchronization as XLA collectives over ICI/DCN instead of NCCL/MPI.

Subpackages
-----------
runtime   process bootstrap, topology discovery, mesh construction
          (incl. multi-slice hybrid DCN x ICI meshes)
parallel  strategies (SingleDevice / DataParallel incl. hierarchical /
          AutoSharded / KVStore), collectives adapter, ring & Ulysses
          sequence parallelism, 4D megatron (dp x sp x pp x tp + ep)
models    MLP / MNIST-CNN / PyramidNet / ResNet-50 / TransformerLM /
          CaffeNet (prototxt-built) flax modules
ops       flash attention (Pallas TPU kernel), RoPE, classification losses
data      dataset registry, sharded sampling, Python + native C++ loaders
train     jitted step engines and five API flavors: imperative loop,
          Keras fit(), Chainer Trainer, TF1 Estimator, Caffe Solver
ckpt      leader-gated checkpointing (weights / per-epoch / full state)
metrics   metrics bus (stdout / JSONL / TensorBoard sinks)
obs       observability: span tracer (Chrome trace / Perfetto export),
          recompile sentinel, goodput/MFU accounting, streaming
          latency-percentile histograms — one Observer facade that
          every loop flavor and the serve scheduler accept
resil     fault tolerance: deterministic FaultPlan injection harness,
          on-device step anomaly guard (skip/rollback/raise), SIGTERM
          preemption watcher; checkpoint integrity + serve containment
          live in ckpt/ and serve/
launch    local, TPU-VM slice, and SLURM launchers (fail-fast +
          checkpoint-restart elasticity)
utils     flags, seeding, timing, profiling, prototxt parsing
"""

__version__ = "0.1.0"

from dtdl_tpu.runtime.mesh import build_mesh, hybrid_mesh, local_mesh  # noqa: F401
from dtdl_tpu.runtime.bootstrap import initialize, is_leader  # noqa: F401
from dtdl_tpu.obs import Observer  # noqa: F401
