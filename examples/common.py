"""Shared plumbing for the example scripts.

The reference duplicates its CLI/data/model blocks in every script (SURVEY
§2.4 notes the three identical TF2 Net/DataSet copies); the examples here
factor that into one module and keep each script focused on the distributed
idiom it demonstrates.  Flag names mirror the reference scripts, both
spellings accepted (dtdl_tpu.utils.config).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
import optax

from dtdl_tpu.data import (
    CIFAR10_MEAN, CIFAR10_STD, DataLoader, ShardedSampler,
    cifar10_train_transform, load_dataset, normalize_transform,
)
from dtdl_tpu.runtime import initialize, is_leader
from dtdl_tpu.runtime.compile_cache import enable_compile_cache
from dtdl_tpu.runtime.topology import banner
from dtdl_tpu.utils.config import parse_mesh_shape


def bootstrap(args):
    """Rendezvous (if multi-process) and print the leader banner.

    ``--platform cpu --fake-devices 8`` switches to a virtual CPU mesh
    through jax.config, which must happen before the first backend use —
    so it is the first thing every example does.  The persistent compile
    cache is placed here too (dtdl_tpu/runtime/compile_cache.py).
    """
    enable_compile_cache()
    if getattr(args, "platform", ""):
        jax.config.update("jax_platforms", args.platform)
        if args.platform == "cpu" and getattr(args, "fake_devices", 0):
            jax.config.update("jax_num_cpu_devices", args.fake_devices)
    topo = {"coordinator": getattr(args, "coordinator", ""),
            "num_processes": getattr(args, "num_processes", 1),
            "process_id": getattr(args, "process_id", 0)}
    if not topo["coordinator"]:
        # inside a multi-task SLURM allocation every script is launchable
        # with zero flags (the reference only advertised this; README.md:11)
        from dtdl_tpu.launch.slurm import maybe_slurm
        topo = maybe_slurm() or topo
    initialize(**topo)
    if is_leader():
        print(banner(), flush=True)


def build_mesh_from_args(args):
    from dtdl_tpu.runtime import build_mesh
    spec = parse_mesh_shape(args)
    if spec is None:
        return build_mesh()
    shape, axes = spec
    return build_mesh(shape, axes)


def _host_batch_and_sampler(n_examples: int, global_batch: int, *,
                            shuffle: bool, seed: int):
    """(per-host batch, this host's ShardedSampler) — the one place the
    global-batch split and dataset partition are decided."""
    nproc = jax.process_count()
    if global_batch % nproc:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"{nproc} processes")
    sampler = ShardedSampler(n_examples, nproc, jax.process_index(),
                             shuffle=shuffle, seed=seed)
    return global_batch // nproc, sampler


def per_process_loader(images, labels, global_batch: int, *, shuffle: bool,
                       seed: int, transform=None, drop_last: bool = True):
    """Loader feeding this host's stripe of the global batch."""
    batch, sampler = _host_batch_and_sampler(
        len(labels), global_batch, shuffle=shuffle, seed=seed)
    return DataLoader({"image": images, "label": labels}, batch,
                      sampler=sampler, drop_last=drop_last,
                      transform=transform)


def _limit(args, train, test):
    (xtr, ytr), (xte, yte) = train, test
    for name in ("limit_train", "limit_test"):
        if getattr(args, name, 0) < 0:
            raise ValueError(f"--{name.replace('_', '-')} must be >= 0")
    if getattr(args, "limit_train", 0):
        xtr, ytr = xtr[: args.limit_train], ytr[: args.limit_train]
    if getattr(args, "limit_test", 0):
        xte, yte = xte[: args.limit_test], yte[: args.limit_test]
    return (xtr, ytr), (xte, yte)


def cifar_loaders(args, seed: int):
    """CIFAR-10 train/val loaders with the reference's augmentation
    (RandomCrop(32, pad 4) + flip + normalize, reference
    pytorch/single_gpu.py:51-55).

    ``--num-workers N`` (N > 0) routes the train pipeline through the native
    C++ producer/consumer loader — augment/normalize/batch on N worker
    threads, the role torch DataLoader's ``num_workers=4`` processes play
    for the reference (pytorch/single_gpu.py:21,60-61).  Both paths use the
    same ShardedSampler (per-host stripe of a per-epoch global
    permutation), so the loader backend never changes which examples a host
    trains on or the cross-host mixing semantics.
    """
    (xtr, ytr), (xte, yte) = _limit(
        args, *load_dataset("cifar10", args.dataset_dir,
                            download=getattr(args, "download", True)))
    workers = getattr(args, "num_workers", 0)
    if workers > 0:
        from dtdl_tpu.data.native_loader import NativeDataLoader
        batch, sampler = _host_batch_and_sampler(
            len(ytr), args.batch_size, shuffle=True, seed=seed)
        train = NativeDataLoader.or_python(
            xtr, ytr, batch, seed=seed, augment=True,
            mean=CIFAR10_MEAN, std=CIFAR10_STD, n_threads=workers,
            sampler=sampler)
        if jax.process_index() == 0:
            print(f"train loader: {type(train).__name__} "
                  f"({workers} workers)", flush=True)
    else:
        train = per_process_loader(
            xtr, ytr, args.batch_size, shuffle=True, seed=seed,
            transform=cifar10_train_transform(CIFAR10_MEAN, CIFAR10_STD))
    val = per_process_loader(
        xte, yte, args.batch_size, shuffle=False, seed=seed,
        transform=normalize_transform(CIFAR10_MEAN, CIFAR10_STD),
        drop_last=False)
    return train, val


def mnist_arrays(args, flatten: bool = False):
    return _limit(args, *load_dataset("mnist", args.dataset_dir,
                                      flatten=flatten))


def sgd_steplr(lr: float, momentum: float, weight_decay: float,
               steps_per_epoch: int, step_epochs: int = 2,
               gamma: float = 0.1):
    """SGD + StepLR(step=2 epochs, gamma=0.1) — the reference DDP optimizer
    (reference pytorch/distributed_data_parallel.py:94-97)."""
    schedule = optax.exponential_decay(
        lr, transition_steps=step_epochs * steps_per_epoch,
        decay_rate=gamma, staircase=True)
    tx = optax.chain(
        optax.add_decayed_weights(weight_decay),
        optax.sgd(schedule, momentum=momentum),
    )
    return tx, schedule
