"""Weights from the seed: one generator for the program and the reference.

The benchmark, not the program, makes the weights, so that the plain
reference can make the same ones again from the seed and takes nothing the
program has made.  A leaf is addressed by its path in the program's
parameter tree (``block_0/attn/q/kernel``); its key is the seed's key
folded with a CRC of that path, so the values do not depend on the order
or the number of leaves.  How a leaf is drawn (its mean and standard
deviation, from its path and shape) is its family's to say
(``lib/modules.py``): the callers hand ``leaf_moments`` in.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any whole number up to 2**63 (PRNGKey itself stops at 32
    bits without x64)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def leaf_moments(path: str, shape) -> tuple[float, float]:
    """``(mean, std)`` of a leaf's normal draw: the dense family's rule
    (``families/olmo.py`` binds it)."""
    name = path.split("/")
    if name[-1] == "scale":             # norm scale: 1 +- 10%
        return 1.0, 0.1
    if name[-1] == "embed":
        return 0.0, 0.02
    if name[-1] == "kernel":
        # the attention output contracts (heads, head_dim); all others
        # contract their first axis
        fan_in = shape[0] * shape[1] if name[-2] == "out" else shape[0]
        return 0.0, 1.0 / math.sqrt(fan_in)
    raise ValueError(f"no rule to generate parameter leaf {path!r}")


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw_leaf(leaf_key_, shape, mean, std):
    """The leaf itself, f32, from its own key; ``mean`` and ``std`` may be
    traced scalars."""
    return mean + std * jax.random.normal(leaf_key_, shape, jnp.float32)


def make_leaf(key, path: str, shape, leaf_moments):
    return draw_leaf(leaf_key(key, path), shape, *leaf_moments(path, shape))


def make_params(key, shapes: dict, leaf_moments) -> dict:
    """``{path: shape}`` -> ``{path: f32 array}``; call it under ``jit``."""
    return {p: make_leaf(key, p, s, leaf_moments) for p, s in shapes.items()}
