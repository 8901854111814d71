"""What decides ``correct`` for a training cell.

The program's first :data:`STEPS` steps (driven in set-up through the
window's own call and feed) are followed by the plain reference on the same
seed's weights and batches.  Two steps, not three: at the cells' sizes the
f32 reference takes up to 4.4 s a step on the chip, and three would outlast
the 10 s window (PERF.md, Findings PR 24).  From each side, of the first
gradient as the optimizer got it (Adam's first moment after one step, over
1 - b1) and of the parameters' change over those steps, every leaf gives
its norm and :data:`PROJECTIONS` sums under seeded random signs
(:func:`leaf_readings`, one small program a leaf): a few scalars a leaf, so neither side ever holds
the other's tensors.  Compared, each against a limit of its own from the
cell's file:

* ``loss{1,2}_gap`` - |program - reference| / reference, each step;
* ``grad_gap``, ``change_gap`` - the gap between the two sides' *norms* of
  a leaf, by the worst leaf;
* ``grad_dir_gap``, ``change_dir_gap`` - the root mean square of the gaps
  between the two sides' signed sums of a leaf (an estimate of the norm of
  their difference that needs no tensor of the other side), by the worst
  leaf, and ``*_dir_gap_median`` by the median leaf.  Rounding that is
  unbiased, as the float8 control's, moves a norm in the second order only
  and a signed sum in the first: these are the numbers that separate the
  control from the program (PERF.md, Findings PR 24).

Every leaf's gap is measured against the reference's norm of that leaf or
of the median leaf, whichever is larger.  Leaves whose reference gradient
is nought to rounding (under a thousandth of the median leaf's) move under
Adam by round-off alone and are left out of the change's numbers.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import modules
from . import reference as ref
from . import tokens as tok
from . import weights

STEPS = 2
PROJECTIONS = 32          # signed sums a leaf (the bits of one random word)
NEVER = 1e30              # a gap that is not a number (JSON has no inf)
DEAD_GRADIENT = 1e-3     # of the median leaf's reference gradient norm


def faults_of(chips: int) -> tuple:
    """The faults a cell on ``chips`` chips can have: on one chip there is
    no exchange to leave out."""
    return ("half_batch", "state_unchanged") + (
        ("exchange_left_out",) if chips > 1 else ())


def _read(x, leaf_key):
    bits = jax.random.bits(jax.random.fold_in(leaf_key, 1), x.shape,
                           jnp.uint32)
    proj = lax.map(lambda j: jnp.sum(jnp.where((bits >> j) & 1, -x, x)),
                   jnp.arange(PROJECTIONS, dtype=jnp.uint32))
    return jnp.sqrt(jnp.sum(jnp.square(x))), proj


@jax.jit
def _leaf_reading(x, leaf_key, scale):
    return _read(x.astype(jnp.float32) * scale, leaf_key)


@jax.jit
def _leaf_change_reading(x, leaf_key, mean, std):
    return _read(x.astype(jnp.float32) - weights.draw_leaf(
        leaf_key, x.shape, mean, std), leaf_key)


def _by_leaf(read, tree: dict) -> dict:
    norm, proj = {}, {}
    for p, x in tree.items():
        norm[p], proj[p] = read(p, x)
    return {"norm": norm, "proj": proj}


def leaf_readings(tree: dict, key, scale: float = 1.0) -> dict:
    """``{"norm": {leaf: scalar}, "proj": {leaf: [PROJECTIONS]}}`` of
    ``scale`` times a ``{path: array}`` tree.  A projection is the sum of
    the leaf under random signs drawn from the seed's key and the leaf's
    path (bit ``j`` of one random word an element), the same on both
    sides.  Not to be called under ``jit``: it runs one small program a
    leaf, compiled once a shape, so that set-up does not trace every leaf
    of the tree anew in every run, and a leaf's temporaries never outlive
    its program."""
    return _by_leaf(lambda p, x: _leaf_reading(
        x, weights.leaf_key(key, p), scale), tree)


def change_readings(params: dict, key, leaf_moments) -> dict:
    """:func:`leaf_readings` of ``params - (the seed's initial weights)``,
    which are made again from the seed (by the family's ``leaf_moments``)
    inside each leaf's program, never kept."""
    return _by_leaf(lambda p, x: _leaf_change_reading(
        x, weights.leaf_key(key, p), *leaf_moments(p, x.shape)), params)


def reference_step(cfg: dict, lr: float, precision: str = "f32",
                   fault: str | None = None):
    """``step(params, m, v, tokens, t) -> (params, m, v, loss, grads)``:
    one step of the plain reference (the family's forward pass, the shared
    AdamW), not yet under ``jit``."""
    hyper = dict(ref.ADAMW, lr=lr)
    loss_and_grads = modules.family_of(cfg).loss_and_grads

    def step(params, m, v, tokens, t):
        loss, grads = loss_and_grads(params, tokens, cfg, precision)
        if fault != "state_unchanged":
            params, m, v = ref.adamw_update(params, m, v, grads, t, **hyper)
        return params, m, v, loss, grads

    return step


def reference_readings(cfg: dict, shapes: dict, seed: int, rows: int,
                       row_tokens: int, distribution: str, lr: float,
                       precision: str = "f32", fault: str | None = None,
                       device=None, chips: int = 1) -> dict:
    """Follow the first ``STEPS`` steps; returns on the host
    ``{"loss": [STEPS], "grad": readings, "change": readings}`` with
    :func:`leaf_readings`' form.

    ``precision`` other than ``"f32"`` makes this the control; ``fault``
    plants one of :func:`faults_of` ``chips`` in the reference put in the
    program's place: no update, the mean over the first half of the rows,
    or over the first chip's rows alone (what a data-parallel step applies
    when the gradients' exchange between the ``chips`` is left out)."""
    if fault not in (None,) + faults_of(chips):
        raise ValueError(f"unknown fault {fault!r} on {chips} chip(s)")
    kept_rows = {"half_batch": max(1, rows // 2),
                 "exchange_left_out": rows // chips}.get(fault, rows)
    key = weights.seed_key(seed)
    leaf_moments = modules.family_of(cfg).leaf_moments

    @jax.jit
    def init(key):
        p = weights.make_params(key, shapes, leaf_moments)
        zeros = jax.tree.map(jnp.zeros_like, p)
        return p, zeros, jax.tree.map(jnp.zeros_like, p)

    step = jax.jit(reference_step(cfg, lr, precision, fault),
                   donate_argnums=(0, 1, 2))
    with jax.default_device(device or jax.devices()[0]):
        params, m, v = init(key)
        losses, grad = [], None
        for i in range(STEPS):
            batch = tok.batch_tokens(seed, i, rows, row_tokens,
                                     cfg["vocab_size"], distribution)
            params, m, v, loss, grads = step(
                params, m, v, jnp.asarray(batch[:kept_rows]),
                jnp.float32(i + 1))
            losses.append(loss)
            if i == 0:
                grad = leaf_readings(grads, key)
            del grads
        change = change_readings(params, key, leaf_moments)
        out = jax.device_get({"loss": losses, "grad": grad, "change": change})
    del params, m, v
    return on_host(out)


def on_host(out: dict) -> dict:
    """A side's readings as plain floats and lists (JSON can hold them)."""
    def plain(read):
        return {"norm": {k: float(x) for k, x in read["norm"].items()},
                "proj": {k: [float(y) for y in x]
                         for k, x in read["proj"].items()}}
    return {"loss": [float(x) for x in out["loss"]],
            "grad": plain(out["grad"]), "change": plain(out["change"])}


def _leaf_gaps(prog: dict, refr: dict, leaves) -> tuple[dict, dict]:
    """``({leaf: norm gap}, {leaf: direction gap})`` of one quantity's
    readings, each against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    median = statistics.median(refr["norm"][k] for k in leaves)

    def finite(gap):
        return float(gap) if np.isfinite(gap) else NEVER

    norm_gap, dir_gap = {}, {}
    for k in leaves:
        scale = max(refr["norm"][k], median)
        apart = np.subtract(prog["proj"][k], refr["proj"][k])
        norm_gap[k] = finite(abs(prog["norm"][k] - refr["norm"][k]) / scale)
        dir_gap[k] = finite(np.sqrt(np.mean(np.square(apart))) / scale)
    return norm_gap, dir_gap


def numbers(prog: dict, refr: dict) -> dict:
    """The numbers compared, ``{name: value}``, plus ``*_leaf`` notes."""
    out = {}
    for i in range(STEPS):
        p, r = prog["loss"][i], refr["loss"][i]
        gap = float(abs(p - r) / abs(r))
        out[f"loss{i + 1}_gap"] = gap if np.isfinite(gap) else NEVER
    norms = refr["grad"]["norm"]
    leaves = sorted(norms)
    median_g = statistics.median(norms.values())
    live = [k for k in leaves if norms[k] >= DEAD_GRADIENT * median_g]
    for what, over in (("grad", leaves), ("change", live)):
        norm_gap, dir_gap = _leaf_gaps(prog[what], refr[what], over)
        for name, gaps in ((f"{what}_gap", norm_gap),
                           (f"{what}_dir_gap", dir_gap)):
            where = max(gaps, key=gaps.get)
            out[name], out[name + "_leaf"] = gaps[where], where
        out[f"{what}_dir_gap_median"] = statistics.median(dir_gap.values())
    out["leaves_compared"] = len(live)
    return out


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the limits' names."""
    table = {k: {"value": nums[k], "limit": float(lim)}
             for k, lim in limits.items()}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return bool(ok), table
