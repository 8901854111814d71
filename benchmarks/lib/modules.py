"""The benchmark's files that are found by name and loaded as modules:
runners, metric readers, and a configuration's family.

A **family** is ``families/<model_type>.py`` beside the ``configs/``
directory that holds the configuration's file (``run.py:load_config`` puts
that path into the configuration as ``cfg["family"]``).  It holds everything
the benchmark asks of a model family, five names and nothing else:

* ``model_kwargs(cfg, remat)`` - the program's keywords for the
  configuration file's keys (what ``runners/train.py`` builds the model of);
* ``leaf_moments(path, shape)`` - ``(mean, std)`` of a parameter leaf's
  normal draw (``lib/weights.py`` makes the leaf, on both sides);
* ``loss_and_grads(params, tokens, cfg, precision)`` - the plain reference's
  forward pass, loss and gradients (``lib/correct.py`` follows it);
* ``train_flops(cfg, rows, row_tokens)`` - model FLOPs of one train step
  (``metrics/step_mfu.py``);
* ``attention_work(cfg, rows_per_chip, positions)`` - ``{"flops", "bytes"}``
  of one step's attention on a chip (``metrics/flash_roofline.py``).
"""

from __future__ import annotations

import functools
import importlib.util
import os


def load_file(path: str, kind: str):
    """The module in the file ``path``; a missing one is an error that
    names the path."""
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind} file {path}")
    stem = os.path.basename(path)[: -len(".py")]
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{stem.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def _family(path: str):
    return load_file(path, "families")


def family_of(cfg: dict):
    """The family of a configuration that ``run.py:load_config`` loaded."""
    return _family(cfg["family"])
