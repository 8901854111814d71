"""Classification loss ops.

The reference computes softmax cross-entropy through each host framework
(``nn.CrossEntropyLoss`` at reference pytorch/distributed_data_parallel.py:93,
Keras ``sparse_categorical_crossentropy`` at tensorflow2/mnist_single.py:87,
Chainer ``L.Classifier`` default at chainer/train_mnist.py:62).  Here it is
one op: a numerically stable log-sum-exp formulation that XLA fuses into the
final matmul's epilogue.  For the 10-class parity workloads XLA's fusion is
already optimal; a fused Pallas kernel only pays off at large vocab sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          reduction: str = "mean") -> jax.Array:
    """Cross-entropy from integer labels; logits (B, C), labels (B,)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(
        logits, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    losses = lse - true_logit
    if reduction == "mean":
        return losses.mean()
    if reduction == "sum":
        return losses.sum()
    if reduction == "none":
        return losses
    raise ValueError(f"unknown reduction {reduction!r}")


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Fraction of argmax predictions matching integer labels."""
    return (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32).mean()


# ---------------------------------------------------------------------------
# vocab-chunked LM head loss (never materializes [tokens, vocab] logits)
# ---------------------------------------------------------------------------

def _ce_chunks(V: int, chunk_size: int) -> tuple[int, int]:
    vc = min(max(int(chunk_size), 1), V)
    return -(-V // vc), vc


def _vary_like(x, *refs):
    """shard_map VMA pre-cast for scan carries — delegates to the single
    implementation (lazy import: dtdl_tpu.parallel pulls in the megatron
    stack, which itself imports dtdl_tpu.ops)."""
    from dtdl_tpu.parallel.collectives import pvary_like
    return pvary_like(x, *refs)


def _chunk_logits(h, emb, c, vc, V):
    """f32 logits of vocab chunk c: ([T, vc], global col ids, valid mask).

    When the last chunk would run past V the window slides back to keep
    static shapes; columns already covered by the previous chunk come back
    with ``valid=False`` and their logits forced to -inf.
    """
    start = c * vc
    base = jnp.minimum(start, V - vc)
    emb_c = jax.lax.dynamic_slice_in_dim(emb, base, vc, 0)
    cols = base + jnp.arange(vc)
    valid = cols >= start
    logits = jnp.einsum("td,vd->tv", h.astype(jnp.float32),
                        emb_c.astype(jnp.float32))
    logits = jnp.where(valid[None, :], logits, -jnp.inf)
    return logits, cols, valid


def chunked_lm_loss(h, emb, targets, mask, chunk_size=4096):
    """Masked-sum LM cross entropy with the vocab dim processed in chunks.

    ``h`` [T, D] final hidden states, ``emb`` [V, D] (tied) output
    embedding, ``targets`` [T] int32, ``mask`` [T] f32.  Returns
    ``(loss_sum, correct_sum)`` where correct counts argmax==target hits
    (masked), so callers get accuracy without logits.

    The flash-attention trick applied to the vocab axis: an online
    (max, sumexp) recurrence over [T, chunk] logit tiles — peak memory is
    O(T * chunk) instead of the O(T * V) f32 logits the dense head
    materializes for itself *and* for its backward residual (at V=32k,
    seq 4k, batch 8 that is 2 x 4.2 GB).  The backward pass recomputes
    each tile from the saved (h, lse) — the same recompute-not-store
    contract as dtdl_tpu/ops/attention.py.

    Under ``shard_map`` a mask the caller built from constants is
    replicated while its cotangent varies like ``h``; a custom VJP needs
    the two to agree, so the mask is cast to vary like the rest here.
    """
    return _chunked_lm_loss(h, emb, targets,
                            _vary_like(mask, h, emb, targets), chunk_size)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_lm_loss(h, emb, targets, mask, chunk_size):
    (loss, correct), _ = _chunked_fwd(h, emb, targets, mask, chunk_size)
    return loss, correct


def _chunked_fwd(h, emb, targets, mask, chunk_size):
    V = emb.shape[0]
    n, vc = _ce_chunks(V, chunk_size)
    T = h.shape[0]
    tgt = targets.astype(jnp.int32)

    def step(carry, c):
        m, s, true_l, best, arg = carry
        logits, cols, valid = _chunk_logits(h, emb, c, vc, V)
        cmax = jnp.max(logits, -1)
        m_new = jnp.maximum(m, cmax)
        s = (s * jnp.exp(m - m_new)
             + jnp.sum(jnp.exp(logits - m_new[:, None]), -1))
        hit = (tgt[:, None] == cols[None, :]) & valid[None, :]
        true_l = true_l + jnp.sum(jnp.where(hit, logits, 0.0), -1)
        carg = cols[jnp.argmax(logits, -1)]
        arg = jnp.where(cmax > best, carg, arg)
        best = jnp.maximum(best, cmax)
        return (m_new, s, true_l, best, arg), None

    neg = _vary_like(jnp.full((T,), -jnp.inf, jnp.float32), h, emb, targets)
    zero = _vary_like(jnp.zeros((T,), jnp.float32), h, emb, targets)
    arg0 = _vary_like(jnp.zeros((T,), jnp.int32), h, emb, targets)
    (m, s, true_l, _, arg), _ = jax.lax.scan(
        step, (neg, zero, zero, neg, arg0), jnp.arange(n))
    lse = m + jnp.log(s)
    loss = jnp.sum((lse - true_l) * mask)
    correct = jnp.sum((arg == tgt).astype(jnp.float32) * mask)
    return (loss, correct), (h, emb, targets, mask, lse, true_l, arg)


def _chunked_bwd(chunk_size, res, cot):
    h, emb, targets, mask, lse, true_l, arg = res
    g = cot[0]                  # cotangent of loss_sum
    V, D = emb.shape
    n, vc = _ce_chunks(V, chunk_size)
    tgt = targets.astype(jnp.int32)
    w = (mask * g).astype(jnp.float32)

    def step(carry, c):
        dh, demb = carry
        logits, cols, valid = _chunk_logits(h, emb, c, vc, V)
        p = jnp.where(valid[None, :], jnp.exp(logits - lse[:, None]), 0.0)
        onehot = ((tgt[:, None] == cols[None, :]) & valid[None, :]
                  ).astype(jnp.float32)
        dl = (p - onehot) * w[:, None]              # [T, vc]
        base = jnp.minimum(c * vc, V - vc)
        emb_c = jax.lax.dynamic_slice_in_dim(emb, base, vc, 0)
        dh = dh + jnp.einsum("tv,vd->td", dl, emb_c.astype(jnp.float32))
        demb_c = jnp.einsum("tv,td->vd", dl, h.astype(jnp.float32))
        # in-place tile accumulate: one pass, no stacked [n, vc, D] copy
        # (overlap columns of a slid-back last tile contribute zeros)
        cur = jax.lax.dynamic_slice_in_dim(demb, base, vc, 0)
        demb = jax.lax.dynamic_update_slice_in_dim(demb, cur + demb_c,
                                                   base, 0)
        return (dh, demb), None

    dh0 = _vary_like(jnp.zeros(h.shape, jnp.float32), h, emb, targets, g)
    demb0 = _vary_like(jnp.zeros((V, D), jnp.float32), h, emb, targets, g)
    (dh, demb), _ = jax.lax.scan(step, (dh0, demb0), jnp.arange(n))
    # loss term + the correct_sum output's own mask-cotangent (argmax hits
    # are piecewise-constant in h/emb, so their grads through correct are 0)
    dmask = (lse - true_l) * g + (arg == tgt).astype(jnp.float32) * cot[1]
    dtargets = np.zeros(targets.shape, jax.dtypes.float0)
    return dh.astype(h.dtype), demb.astype(emb.dtype), dtargets, dmask


_chunked_lm_loss.defvjp(_chunked_fwd, _chunked_bwd)
