"""Rotary position embeddings (RoPE).

Shared by the flax TransformerLM (dtdl_tpu/models/transformer.py) and the
manual-SPMD megatron step (dtdl_tpu/parallel/megatron.py).  Position-offset
aware so sequence-parallel shards can rotate their *global* positions
(device i of a ``seq`` axis passes ``offset = i * seq_local``).

Two consumers, two shapes of the same math:

* :func:`apply_rope` — the eager rotation, used by the decode paths (one
  or a handful of query rows against a KV cache — the rotation is noise
  there) and as the numerics oracle.
* the **fused kernel path** (round 13) — training/eval full-sequence
  attention passes the raw (cos, sin) tables to
  ``flash_attention(..., rope=(cos, sin))`` and the rotation happens
  inside the Pallas kernels on tile load, eliminating apply_rope's
  per-layer HBM round-trip of the full [B, H, S, D] Q/K tensors.
  :func:`rope_rows` builds the per-position full-width (D, not D/2)
  table rows the kernels consume: with cc = [c, c] and ss = [s, s],
  ``rope(x) = x·cc + rot_half(x)·ss`` where rot_half([x1, x2]) =
  [-x2, x1] — the identical f32 arithmetic as :func:`apply_rope`.

The reference has no sequence models (SURVEY §5.7); this op exists for the
framework's first-class long-context capability.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0,
                     dtype=jnp.float32):
    """Precompute (cos, sin) tables of shape [max_seq, head_dim//2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    pos = jnp.arange(max_seq, dtype=jnp.float32)
    angles = jnp.outer(pos, inv_freq)
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def rope_rows(cos, sin, positions):
    """Per-position rope rows widened to full head_dim for the fused
    kernels: [len(positions), head_dim] f32 (cc, ss) such that
    ``x·cc + rot_half(x)·ss`` equals :func:`apply_rope` at those
    positions.  Tiny ([S, D] vs the [B, H, S, D] tensors), so gathering
    them outside the kernel costs ~1/(2·B·H) of the traffic the fusion
    removes."""
    c = jnp.take(cos, positions, axis=0).astype(jnp.float32)
    s = jnp.take(sin, positions, axis=0).astype(jnp.float32)
    return (jnp.concatenate([c, c], axis=-1),
            jnp.concatenate([s, s], axis=-1))


def apply_rope(x, cos, sin, offset=0, positions=None, interleave=False):
    """Rotate [batch, heads, seq, head_dim] queries/keys.

    ``offset`` (int or traced scalar) is the global position of the shard's
    first token — the hook contiguous sequence parallelism uses.
    ``positions`` ([seq] int array, overrides ``offset``) gives each local
    row an arbitrary global position — the hook the zigzag ring layout uses
    (dtdl_tpu/parallel/sequence.py zigzag_positions).

    Frequency ``i`` turns the pair of dims ``(i, i + head_dim/2)`` (the two
    halves), or with ``interleave`` the adjacent pair ``(2i, 2i + 1)``, in
    place (a config's ``rope_interleave``).
    """
    seq = x.shape[2]
    if positions is not None:
        c = jnp.take(cos, positions, axis=0)
        s = jnp.take(sin, positions, axis=0)
    elif isinstance(offset, int) and offset == 0:
        c, s = cos[:seq], sin[:seq]
    else:
        c = jnp.take(cos, offset + jnp.arange(seq), axis=0)
        s = jnp.take(sin, offset + jnp.arange(seq), axis=0)
    c = c[None, None, :, :]
    s = s[None, None, :, :]
    if interleave:
        pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)
