"""The plain reference: a decoder-only LM, its loss, gradients and AdamW
update in straightforward float32 ``jax.numpy``.  No kernels, no cache, no
import of the program.

Architecture (what the configurations' ``departures`` leave of OLMo):
pre-RMSNorm blocks (learned scale, eps from the config), multi-head
attention with full rotary embedding (half-split "rotate half" layout,
theta from the config), SwiGLU MLP, no biases, final RMSNorm, head tied to
the embedding, mean next-token cross entropy.

Parameters are a flat ``{path: array}`` dict with the program's paths
(``embed``, ``block_i/ln_attn/scale``, ``block_i/attn/{q,k,v}/kernel``
[d, H, D], ``block_i/attn/out/kernel`` [H, D, d],
``block_i/ln_mlp/scale``, ``block_i/mlp/{wi,wg,wo}/kernel``,
``ln_f/scale``), made by :mod:`weights` from the seed.

``precision`` selects how every matmul is computed:

* ``"f32"``  — float32 at ``highest`` (the reference proper);
* ``"bf16"`` — operands rounded to bfloat16, f32 accumulate (what the
  configurations state; a witness, not a control);
* ``"fp8"``  — operands rounded to float8_e4m3fn with a per-tensor amax
  scale, f32 accumulate: the nearest precision below bfloat16, the step
  that would tempt a later PR.  This is the control.

Memory: a row at a time (``lax.scan`` over the batch) and every block
under ``jax.checkpoint``, so the f32 scores of one row of one layer are
the largest temporary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

ADAMW = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}
_E4M3_MAX = 448.0


def _round_operand(x, precision: str):
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = _E4M3_MAX / amax
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return q / scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, a, b, precision: str):
    # straight-through rounding: the backward matmuls round their own
    # operands the same way, as a low-precision training step would
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, _round_operand(a, precision),
                          _round_operand(b, precision), precision="highest")

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        ins, out = spec.split("->")
        sa, sb = ins.split(",")
        r = functools.partial(_round_operand, precision=precision)
        da = jnp.einsum(f"{out},{sb}->{sa}", r(g), r(b), precision="highest")
        db = jnp.einsum(f"{sa},{out}->{sb}", r(a), r(g), precision="highest")
        return da, db

    f.defvjp(fwd, bwd)
    if precision == "f32":
        return jnp.einsum(spec, a, b, precision="highest")
    return f(a, b)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, H, D]; rotate-half layout, positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    c, sn = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * sn, x1 * sn + x2 * c], axis=-1)


def _block(x, p, cfg, precision):
    """x [S, d]; p: this block's leaves with the ``block_i/`` prefix cut."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = functools.partial(_mm, precision=precision)
    h = _rms_norm(x, p["ln_attn/scale"], eps)
    q = _rope(mm("sd,dhe->she", h, p["attn/q/kernel"]), theta)
    k = _rope(mm("sd,dhe->she", h, p["attn/k/kernel"]), theta)
    v = mm("sd,dhe->she", h, p["attn/v/kernel"])
    s = x.shape[0]
    scores = mm("qhe,khe->hqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = mm("hqk,khe->qhe", probs, v)
    x = x + mm("she,hed->sd", o, p["attn/out/kernel"])
    h = _rms_norm(x, p["ln_mlp/scale"], eps)
    gate = jax.nn.silu(mm("sd,df->sf", h, p["mlp/wg/kernel"]))
    up = mm("sd,df->sf", h, p["mlp/wi/kernel"])
    return x + mm("sf,fd->sd", gate * up, p["mlp/wo/kernel"])


def row_loss_sum(params: dict, row, cfg: dict, precision: str = "f32"):
    """Summed next-token cross entropy of one row of tokens [S]."""
    inputs, targets = row[:-1], row[1:]
    x = jnp.take(params["embed"], inputs, axis=0)
    block = jax.checkpoint(
        functools.partial(_block, cfg=cfg, precision=precision))
    for i in range(cfg["num_hidden_layers"]):
        pre = f"block_{i}/"
        x = block(x, {k[len(pre):]: v for k, v in params.items()
                      if k.startswith(pre)})
    x = _rms_norm(x, params["ln_f/scale"], cfg["rms_norm_eps"])
    logits = _mm("sd,vd->sv", x, params["embed"], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - true)


def loss_and_grads(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """Mean loss over all targets of ``tokens`` [B, S], and its gradient."""
    def one(carry, row):
        l, g = jax.value_and_grad(row_loss_sum)(params, row, cfg, precision)
        return (carry[0] + l, jax.tree.map(jnp.add, carry[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = lax.scan(one, zero, tokens)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    return loss / n, jax.tree.map(lambda g: g / n, grads)


def adamw_update(params, m, v, grads, t, lr, b1, b2, eps, weight_decay):
    """One AdamW step as ``optax.adamw`` defines it; ``t`` counts from 1."""
    def leaf(p, m_, v_, g):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        u = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
        return p - lr * (u + weight_decay * p), m_, v_

    out = {k: leaf(params[k], m[k], v[k], grads[k]) for k in params}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})
