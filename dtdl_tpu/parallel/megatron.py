"""Manual-SPMD 4D-parallel transformer train step (dp × sp × pp × tp + ep).

The reference's only parallelism is batch data-parallel over NCCL (SURVEY
§2.2); this module is the framework's scale path beyond it: one
``shard_map`` over a 4-axis mesh ``('data', 'seq', 'pipe', 'model')``
composing every distributed-training dimension, with all collectives
explicit so they can be audited and scheduled:

* **dp**  — batch sharded over 'data'; gradient reduction falls out of the
  VMA-typed autodiff (the loss psum over 'data' transposes to the allreduce
  DDP fires from its grad hooks, reference
  pytorch/distributed_data_parallel.py:74,132).
* **sp**  — sequence sharded over 'seq' in the **zigzag layout** (each
  shard holds one low + one high chunk, so causal masking is
  load-balanced); **ring attention** rotates K/V via ``lax.ppermute``
  (dtdl_tpu/parallel/sequence.py) — one ICI hop per step, half a block of
  matmul per device per step.
* **pp**  — layers stacked ``[n_stages, layers_per_stage, ...]`` and sharded
  over 'pipe'.  Default schedule is **1F1B** (`_value_and_grad_1f1b`): an
  explicit forward+backward pipeline in one ``lax.scan``, remat per stage,
  vocab-parallel loss head used only on the last stage, activations capped
  at ``min(M, 2S-1)`` microbatch inputs.  ``schedule='gpipe'`` keeps the
  autodiff-through-scan GPipe schedule (`_loss_fn`).
* **tp**  — Megatron column→row parallel attention/MLP over 'model':
  QKV/up projections column-sharded, out/down projections row-sharded, one
  ``psum`` after attention-out and one after MLP-down per block.
* **ep**  — MoE experts sharded over 'model' (expert-parallel on the tensor
  axis).  Default dispatch is **routed**: capacity-factor top-1 routing with
  a token ``lax.all_to_all`` over 'model' to the expert's owner and back
  (dispatch FLOPs linear in tokens; dropped-token fraction reported in the
  step metrics).  ``moe_dispatch='dense'`` keeps the one-hot
  every-local-expert oracle.

Parameters are a plain pytree whose leaves carry global shapes; shard_map's
``in_specs`` (from ``param_specs``) place them.  Everything here is pure
JAX — the flax TransformerLM (dtdl_tpu/models/transformer.py) is the
single-device/GSPMD face of the same architecture.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dtdl_tpu.ops.attention import _use_interpret, flash_attention
from dtdl_tpu.ops.rope import apply_rope, rope_frequencies
from dtdl_tpu.parallel.sequence import (
    ring_attention, zigzag_order, zigzag_positions,
)

DATA, SEQ, PIPE, MODEL = "data", "seq", "pipe", "model"
AXES = (DATA, SEQ, PIPE, MODEL)


@dataclasses.dataclass(frozen=True)
class MegatronConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    d_ff: int = 128
    n_stages: int = 2             # pipeline stages  (== mesh 'pipe' size)
    layers_per_stage: int = 1
    n_experts: int = 0            # 0 = dense MLP; else experts over 'model'
    max_seq: int = 128
    n_microbatches: int = 2
    schedule: str = "1f1b"        # '1f1b' (default) or 'gpipe'
    virtual_stages: int = 1       # v chunks/device: interleaved 1F1B when >1
    moe_dispatch: str = "routed"  # 'routed' (capacity + all-to-all) | 'dense'
    capacity_factor: float = 1.25  # per-expert slots = cf * tokens*k / E
    moe_top_k: int = 1            # experts per token (1 = Switch, 2 = GShard)
    # Switch-style load-balance aux loss weight, ADDED TO THE TRAINING LOSS
    # (not just a metric): capacity-factor routing with no balance pressure
    # collapses onto few experts and drops a growing token fraction — the
    # 0.01 default is the Switch Transformer setting.  0 disables.
    moe_aux_weight: float = 0.01
    dtype: jnp.dtype = jnp.bfloat16
    # fused-rope attend (round 19; ring-fused in kernel round 2): when
    # the 'seq' mesh axis is 1 (TP/PP-only meshes — no ring hops), the
    # local attend IS the whole sequence and rides the Pallas flash
    # kernel with the rotary embedding folded into its tile loads
    # (flash_attention(rope_positions=)), killing the last apply_rope
    # HBM round-trip (8·L·B·H·S·D bytes/step — SCALING.md round 13).
    # Sequence-parallel meshes (seq > 1) fuse through the ring instead:
    # ring_attention(rope=(cos, sin)) rotates each K block *inside* the
    # ppermute schedule at its owner's reconstructed zigzag positions,
    # so the pre-ring apply_rope of K never materializes and the ring
    # carries unrotated blocks — f32-exact vs the unfused path
    # (dtdl_tpu/parallel/sequence.py).  'auto' fuses only on real TPU
    # backends (the CPU fallback runs the flash kernel under the Pallas
    # interpreter, where fusion saves no bytes and costs interpret
    # overhead); True forces it anywhere (the parity tests), False
    # keeps the unfused apply_rope paths.
    fuse_rope: object = "auto"

    def __post_init__(self):
        if self.n_experts and not (1 <= self.moe_top_k <= self.n_experts):
            raise ValueError(
                f"moe_top_k={self.moe_top_k} must be in [1, n_experts="
                f"{self.n_experts}]")

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    @property
    def n_layers(self):
        return self.n_stages * self.layers_per_stage


def factor_mesh(n_devices: int) -> tuple[int, int, int, int]:
    """Cost-aware (data, seq, pipe, model) sizes for ``n_devices``.

    Two regimes:

    * **bootstrap (n <= 8)**: one doubling per axis in model -> pipe -> seq
      order, so small dev/test meshes exercise every parallelism axis
      (8 devices -> the canonical {data 1, seq 2, pipe 2, model 2} the
      test suite runs on).
    * **growth (n > 8)**: extra factors of two go to the axes in
      communication-cost order.  Tensor parallel first, up to 8 — its
      per-layer activation allreduces are the chattiest traffic and must
      stay inside one ICI domain (8 is the per-host chip count on v5e,
      the Megatron-LM default).  Pipeline next, up to 4 — per-hop traffic
      is one activation tensor and latency-tolerant, but the 1F1B bubble
      grows with stage count so it is capped, not greedy.  Sequence
      parallel stays at 2 by default (long-context runs that want more
      pass ``--mesh``).  Data parallelism absorbs everything left,
      including any odd factor — its one grad allreduce per step overlaps
      with the backward pass and is the axis that scales over DCN.

    16 -> (1,2,2,4), 32 -> (1,2,2,8), 64 -> (1,2,4,8), 128 -> (2,2,4,8).
    """
    shape = {"data": 1, "seq": 1, "pipe": 1, "model": 1}
    rem = n_devices
    for ax in ("model", "pipe", "seq"):          # bootstrap doublings
        if rem % 2 == 0:
            shape[ax] *= 2
            rem //= 2
    while rem % 2 == 0 and shape["model"] < 8:   # tp within ICI first
        shape["model"] *= 2
        rem //= 2
    while rem % 2 == 0 and shape["pipe"] < 4:    # then pp
        shape["pipe"] *= 2
        rem //= 2
    shape["data"] *= rem                         # dp takes the rest
    return (shape["data"], shape["seq"], shape["pipe"], shape["model"])


def build_4d_mesh(devices=None) -> Mesh:
    from dtdl_tpu.runtime.mesh import build_mesh
    if devices is None:
        devices = jax.devices()
    return build_mesh(shape=factor_mesh(len(devices)), axes=AXES,
                      devices=devices)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_specs(cfg: MegatronConfig) -> dict:
    """PartitionSpec per parameter (global-shape view).

    Stacked block params lead with a [n_stages, layers_per_stage, ...]
    prefix sharded on 'pipe'; TP shards the head/ff dims on 'model'; expert
    weights shard the expert dim on 'model' (ep-on-tp).
    """
    specs = {
        "embed": P(None, None),            # [V, D] replicated
        "ln_f": P(),                       # [D]
        "blocks": {
            "ln_attn": P(PIPE),            # [st, L, D]
            "wq": P(PIPE, None, None, MODEL),   # [st, L, D, H*hd] col-parallel
            "wk": P(PIPE, None, None, MODEL),
            "wv": P(PIPE, None, None, MODEL),
            "wo": P(PIPE, None, MODEL, None),   # [st, L, H*hd, D] row-parallel
            "ln_mlp": P(PIPE),
        },
    }
    if cfg.n_experts:
        specs["blocks"].update({
            "router": P(PIPE, None, None, None),     # [st, L, D, E]
            "wi": P(PIPE, None, MODEL, None, None),  # [st, L, E, D, F]
            "wg": P(PIPE, None, MODEL, None, None),
            "wo_mlp": P(PIPE, None, MODEL, None, None),  # [st, L, E, F, D]
        })
    else:
        specs["blocks"].update({
            "wi": P(PIPE, None, None, MODEL),   # [st, L, D, F] col-parallel
            "wg": P(PIPE, None, None, MODEL),
            "wo_mlp": P(PIPE, None, MODEL, None),  # [st, L, F, D] row-parallel
        })
    return specs


def init_params(cfg: MegatronConfig, key) -> dict:
    """Global-shape parameter pytree (host-side init, then device_put)."""
    st, L, D = cfg.n_stages, cfg.layers_per_stage, cfg.d_model
    H, F, E = cfg.n_heads * cfg.head_dim, cfg.d_ff, cfg.n_experts
    keys = iter(jax.random.split(key, 16))

    def dense(k, shape):
        fan_in = shape[-2]
        return (jax.random.normal(k, shape, jnp.float32) /
                math.sqrt(fan_in)).astype(jnp.float32)

    blocks = {
        "ln_attn": jnp.ones((st, L, D)),
        "wq": dense(next(keys), (st, L, D, H)),
        "wk": dense(next(keys), (st, L, D, H)),
        "wv": dense(next(keys), (st, L, D, H)),
        "wo": dense(next(keys), (st, L, H, D)),
        "ln_mlp": jnp.ones((st, L, D)),
    }
    if E:
        blocks.update({
            "router": dense(next(keys), (st, L, D, E)),
            "wi": dense(next(keys), (st, L, E, D, F)),
            "wg": dense(next(keys), (st, L, E, D, F)),
            "wo_mlp": dense(next(keys), (st, L, E, F, D)),
        })
    else:
        blocks.update({
            "wi": dense(next(keys), (st, L, D, F)),
            "wg": dense(next(keys), (st, L, D, F)),
            "wo_mlp": dense(next(keys), (st, L, F, D)),
        })
    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, D)) * 0.02,
        "ln_f": jnp.ones((D,)),
        "blocks": blocks,
    }


# ---------------------------------------------------------------------------
# per-stage forward (runs on local shards inside shard_map)
# ---------------------------------------------------------------------------

def _rms(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    return (x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            * scale).astype(x.dtype)


def _attention(cfg, p, x, cos, sin):
    """TP column→row attention with ring attention over 'seq'.

    ``p`` holds one layer's weights (wq/wk/wv [D, H/tp·hd], wo [H/tp·hd, D]).
    """
    b, s_loc, _ = x.shape
    h_loc = p["wq"].shape[-1] // cfg.head_dim    # local heads (H / tp)

    def proj(w):
        y = jnp.einsum("bsd,dh->bsh", x, w.astype(cfg.dtype))
        return y.reshape(b, s_loc, h_loc, cfg.head_dim).transpose(0, 2, 1, 3)

    q, k, v = proj(p["wq"]), proj(p["wk"]), proj(p["wv"])
    # zigzag layout: each 'seq' shard holds one low and one high chunk so
    # causal ring attention is load-balanced; RoPE uses true global
    # positions of the zigzag rows (shard_lm_batch lays the batch out).
    pos = zigzag_positions(SEQ, s_loc)
    sp = lax.axis_size(SEQ)               # static: the mesh is known
    fuse = cfg.fuse_rope
    if fuse == "auto":
        # fuse where the kernels compile through Mosaic, not where they
        # would be interpreted (raises on a platform that is neither)
        fuse = not _use_interpret()
    if fuse and sp == 1:
        # seq axis of 1: no ring hops — the local attend IS the whole
        # sequence, so the rotary embedding rides the flash kernel's
        # HBM→VMEM tile loads (round 13) instead of a per-layer
        # apply_rope round-trip.  zigzag positions are the identity at
        # n=1, so the kernel's index-causal mask == position-causal.
        o = flash_attention(q, k, v, causal=True, rope=(cos, sin),
                            rope_positions=(pos, pos))
    elif fuse:
        # seq axis > 1 (kernel round 2): the rotation rides the ring —
        # q/k go in unrotated and ring_attention rotates each K block
        # at its owner's zigzag positions inside the ppermute schedule,
        # skipping the pre-ring apply_rope materialization of K.
        o = ring_attention(q, k, v, axis_name=SEQ, causal=True,
                           layout="zigzag", rope=(cos, sin))
    else:
        q = apply_rope(q, cos, sin, positions=pos)
        k = apply_rope(k, cos, sin, positions=pos)
        o = ring_attention(q, k, v, axis_name=SEQ, causal=True,
                           layout="zigzag")
    o = o.transpose(0, 2, 1, 3).reshape(b, s_loc, h_loc * cfg.head_dim)
    y = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(cfg.dtype))
    return lax.psum(y, MODEL)                    # row-parallel combine


def _mlp_dense(cfg, p, x):
    wi = p["wi"].astype(cfg.dtype)
    wg = p["wg"].astype(cfg.dtype)
    wo = p["wo_mlp"].astype(cfg.dtype)
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, wg)) * \
        jnp.einsum("bsd,df->bsf", x, wi)
    return lax.psum(jnp.einsum("bsf,fd->bsd", h, wo), MODEL)


def _aux_balance_loss(first_choice_cnt, prob_sum, n_tok_global, n_experts):
    """Switch-Transformer load-balance loss E * <f, p> from GLOBAL stats.

    ``first_choice_cnt``/``prob_sum``/``n_tok_global`` must already be
    summed over every axis that partitions tokens, so the value (and its
    gradient through ``prob_sum``) is identical on every shard — which is
    what lets the dense-dispatch oracle and the routed path compute the
    same number, and the unsharded test oracle reproduce it.  ``f`` (the
    dispatch fractions) comes from argmax counts and is a constant under
    autodiff; the gradient pushes the *probabilities* toward balance.
    Matches the flax MoE module's sow'd aux (models/transformer.py).
    """
    denom = jnp.maximum(n_tok_global, 1.0)
    f = first_choice_cnt / denom
    pbar = prob_sum / denom
    return n_experts * jnp.sum(f * pbar)


def _mlp_moe_routed(cfg, p, x):
    """Capacity-factor top-k routed MoE: token all-to-all over 'model'.

    Real expert parallelism (the dense one-hot path below is the oracle):
    dispatch FLOPs are linear in tokens, not tokens x experts.

    Inside shard_map, ``x`` is MODEL-invariant (every tp shard holds the
    same tokens), so dispatch starts by *partitioning* the token set over
    'model' — each shard routes its T/tp slice (Megatron sequence-parallel
    MoE shape).  Routing takes the top ``cfg.moe_top_k`` experts per token
    (k=1: Switch, gate = raw top prob; k=2: GShard, gates renormalized over
    the chosen pair).  Per (source shard, expert) capacity ``C = ceil(cf *
    T_loc * k / E)`` slots, filled first-choices-first so a second choice
    never evicts a first choice; overflow assignments are *dropped*
    (Switch semantics).  One ``lax.all_to_all`` delivers every expert's
    tokens to the shard that owns it, the expert FFNs run batched over
    [e_loc, tp*C*k, D], and a second all-to-all returns outputs to the
    token's source shard, where they are gathered back to token order,
    gate-combined, and psum-restored to the MODEL-invariant layout every
    block ends with.

    Returns ``(y, (n_dropped, n_assign, aux))``: dropped/total *assignment*
    accounting (psummed over 'model'; the step reports their ratio as
    ``moe_dropped_frac``) and the load-balance aux loss from global router
    stats (`_aux_balance_loss`), which the train step adds to the loss
    with weight ``cfg.moe_aux_weight``.
    """
    e_loc = p["wi"].shape[0]                     # local experts (E / tp)
    tp = lax.axis_size(MODEL)
    my = lax.axis_index(MODEL)
    E = e_loc * tp
    K = cfg.moe_top_k
    b, s, D = x.shape
    T = b * s
    xf = x.reshape(T, D)
    Tp = -(-T // tp) * tp                        # pad to a tp multiple
    if Tp != T:
        xf = jnp.pad(xf, ((0, Tp - T), (0, 0)))
    T_loc = Tp // tp
    xs = lax.dynamic_slice_in_dim(xf, my * T_loc, T_loc, 0)  # my slice
    valid = (my * T_loc + jnp.arange(T_loc)) < T

    logits = jnp.einsum("td,de->te", xs.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, -1)
    topv, topi = lax.top_k(probs, K)             # [T_loc, K]
    if K == 1:
        gate_w = topv                            # Switch: raw top-1 prob
    else:                                        # GShard: renormalized pair
        gate_w = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)
    eid = jnp.where(valid[:, None], topi, E)     # padding routes nowhere

    # load-balance stats over the GLOBAL batch: sum over the 'model' token
    # partition AND the data/seq shards, so every shard holds the same aux
    cnt1 = jnp.sum(jax.nn.one_hot(eid[:, 0], E, dtype=jnp.float32), 0)
    prob_sum = jnp.sum(probs * valid[:, None].astype(jnp.float32), 0)
    n_tok_g = jnp.sum(valid.astype(jnp.float32))
    # pcast to one varying set first: n_tok_g is shape-derived (invariant
    # over data/seq) while cnt1/prob_sum vary — psum rejects mixed states
    cnt1, prob_sum, n_tok_g = lax.psum(
        tuple(_vary(a, (DATA, SEQ, MODEL))
              for a in (cnt1, prob_sum, n_tok_g)),
        (DATA, SEQ, MODEL))
    aux = _aux_balance_loss(cnt1, prob_sum, n_tok_g, E)

    # choice-major flattening: ALL first choices take slots before any
    # second choice, so k=1 behavior is unchanged and a 2nd choice never
    # displaces a 1st
    eidf = eid.T.reshape(K * T_loc)
    validf = jnp.tile(valid, K)
    C = max(1, math.ceil(cfg.capacity_factor * T_loc * K / E))
    oh = jax.nn.one_hot(eidf, E, dtype=jnp.int32)  # zero row for eid == E
    pos = jnp.take_along_axis(jnp.cumsum(oh, 0) - 1,
                              jnp.clip(eidf, 0, E - 1)[:, None], 1)[:, 0]
    kept = (eidf < E) & (pos < C)
    n_drop = jnp.sum((validf & ~kept).astype(jnp.float32))
    n_assign = jnp.sum(validf.astype(jnp.float32))

    # scatter assignments into per-expert slots; out-of-capacity rows drop
    xsk = jnp.tile(xs.astype(cfg.dtype), (K, 1))   # choice-major copies
    send = jnp.zeros((E, C, D), cfg.dtype).at[eidf, pos].set(
        xsk, mode="drop")
    # a2a #1: expert-major chunks -> the shard owning those experts
    recv = lax.all_to_all(send, MODEL, 0, 0, tiled=True)  # [tp*e_loc, C, D]
    toks = recv.reshape(tp, e_loc, C, D).transpose(1, 0, 2, 3)
    toks = toks.reshape(e_loc, tp * C, D)
    wi = p["wi"].astype(cfg.dtype)
    wg = p["wg"].astype(cfg.dtype)
    wo = p["wo_mlp"].astype(cfg.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", toks, wg)) * \
        jnp.einsum("ecd,edf->ecf", toks, wi)
    out = jnp.einsum("ecf,efd->ecd", h, wo)      # [e_loc, tp*C, D]
    # a2a #2: back to each token's source shard, global-expert-id order
    back = out.reshape(e_loc, tp, C, D).transpose(1, 0, 2, 3)
    back = back.reshape(tp * e_loc, C, D)
    ybuf = lax.all_to_all(back, MODEL, 0, 0, tiled=True)  # [E, C, D]
    yk = ybuf.at[eidf, pos].get(mode="fill", fill_value=0)  # [K*T_loc, D]
    w_k = (gate_w.T.reshape(K * T_loc) * kept.astype(jnp.float32))
    y = jnp.sum((yk * w_k.astype(cfg.dtype)[:, None]).reshape(K, T_loc, D),
                axis=0)

    # restore the full MODEL-invariant token set (each shard contributes
    # its slice; the psum is the same row-parallel combine the dense MLP
    # block ends with)
    yfull = jnp.zeros((tp, T_loc, D), cfg.dtype).at[my].set(y)
    yfull = lax.psum(yfull, MODEL).reshape(Tp, D)[:T]
    stats = (lax.psum(n_drop, MODEL), lax.psum(n_assign, MODEL), aux)
    return yfull.reshape(b, s, D), stats


def _mlp_moe(cfg, p, x):
    """Expert-parallel switch MLP: local experts, one-hot dispatch, psum.

    O(tokens x experts) compute — kept as the *oracle* for the routed path
    (``moe_dispatch='dense'``); with ample capacity the two compute the
    identical function, at any ``moe_top_k`` (tests/test_megatron.py).

    Returns ``(y, (0, 0, aux))``: dense dispatch never drops, and the
    load-balance aux uses the same global-stats formula as the routed
    path — here tokens are MODEL-replicated, so the stat psum spans only
    the data/seq shards."""
    e_loc = p["wi"].shape[0]                     # [E/tp, D, F] local experts
    my = lax.axis_index(MODEL)
    E = e_loc * lax.axis_size(MODEL)
    K = cfg.moe_top_k
    router = p["router"]                         # [D, E] replicated
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, -1)
    topv, topi = lax.top_k(probs, K)             # [b, s, K]
    if K == 1:
        gate_w = topv
    else:
        gate_w = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)

    cnt1 = jnp.sum(jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32),
                   axis=(0, 1))
    prob_sum = jnp.sum(probs, axis=(0, 1))
    n_tok = jnp.float32(probs.shape[0] * probs.shape[1])
    cnt1, prob_sum, n_tok = lax.psum(
        tuple(_vary(a, (DATA, SEQ)) for a in (cnt1, prob_sum, n_tok)),
        (DATA, SEQ))
    aux = _aux_balance_loss(cnt1, prob_sum, n_tok, E)

    wi = p["wi"].astype(cfg.dtype)               # [e_loc, D, F]
    wg = p["wg"].astype(cfg.dtype)
    wo = p["wo_mlp"].astype(cfg.dtype)
    y = jnp.zeros(x.shape, cfg.dtype)
    for k in range(K):
        local_id = topi[..., k] - my * e_loc     # position among my experts
        onehot = jax.nn.one_hot(local_id, e_loc, dtype=jnp.float32)
        xe = jnp.einsum("bse,bsd->ebsd", onehot.astype(cfg.dtype), x)
        h = jax.nn.silu(jnp.einsum("ebsd,edf->ebsf", xe, wg)) * \
            jnp.einsum("ebsd,edf->ebsf", xe, wi)
        yk = jnp.einsum("ebsf,efd->bsd", h, wo)
        y = y + lax.psum(yk, MODEL) * gate_w[..., k:k + 1].astype(cfg.dtype)
    zero = jnp.zeros((), jnp.float32)
    return y, (zero, zero, aux)


def _stage_forward(cfg, stage_params, x, cos, sin):
    """Apply this stage's blocks: lax.scan over the stacked layer dim.

    Returns ``(x, (n_dropped, n_assign, aux))`` — per-stage MoE
    dropped-assignment sums (zeros for dense MLP) and the summed
    load-balance aux over this stage's layers, stacked by the scan and
    summed here so the schedules can thread one scalar triple."""
    def block(x, p):
        h = _rms(x, p["ln_attn"])
        x = x + _attention(cfg, p, h, cos, sin)
        h = _rms(x, p["ln_mlp"])
        zero = jnp.zeros((), jnp.float32)
        stats = (zero, zero, zero)
        if cfg.n_experts and cfg.moe_dispatch == "routed":
            y, stats = _mlp_moe_routed(cfg, p, h)
            x = x + y
        elif cfg.n_experts:
            y, stats = _mlp_moe(cfg, p, h)
            x = x + y
        else:
            x = x + _mlp_dense(cfg, p, h)
        return x, stats

    x, stats = lax.scan(block, x, stage_params)
    return x, jax.tree.map(jnp.sum, stats)


# ---------------------------------------------------------------------------
# the GPipe schedule + loss (inside shard_map)
# ---------------------------------------------------------------------------

def _pipeline(cfg, params, x_micro, cos, sin):
    """Run microbatches through the pipe; returns stacked outputs.

    ``x_micro``: [n_micro, mb, s_loc, D] local embedded microbatches.
    Stage s processes tick t's buffer if ``0 <= t - s < n_micro``; a
    ``ppermute`` shifts buffers to the next stage each tick.  Output
    microbatch m leaves the last stage at tick ``m + n_stages - 1``.
    """
    stage = lax.axis_index(PIPE)
    n_stages, n_micro = cfg.n_stages, cfg.n_microbatches
    stage_params = jax.tree.map(lambda a: a[0], params["blocks"])
    # NB: shard_map has already sliced the [n_stages, ...] dim to size 1.

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    mb_shape = x_micro.shape[1:]
    n_ticks = n_micro + n_stages - 1

    def tick(carry, t):
        buf, outputs, drop, tot, auxs = carry
        # stage 0 injects microbatch t (garbage after n_micro ticks, masked)
        inject = lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        buf = jnp.where(stage == 0, inject, buf)
        y, st = _stage_forward(cfg, stage_params, buf, cos, sin)
        # this stage holds real (not garbage/masked) data for tick t iff
        # microbatch t - stage is in range — gate the MoE accounting (the
        # where also zeroes aux-loss cotangents into garbage ticks)
        active = ((t - stage) >= 0) & ((t - stage) < n_micro)
        drop = drop + jnp.where(active, st[0], 0.0)
        tot = tot + jnp.where(active, st[1], 0.0)
        auxs = auxs + jnp.where(active, st[2], 0.0)
        # last stage collects output microbatch t - (n_stages - 1)
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        collect = (stage == n_stages - 1) & (t >= n_stages - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(collect,
                               y.astype(outputs.dtype),
                               lax.dynamic_index_in_dim(
                                   outputs, out_idx, 0, keepdims=False)),
            out_idx, 0)
        buf = lax.ppermute(y, PIPE, perm)
        return (buf, outputs, drop, tot, auxs), None

    # Carry vma: activations vary over the batch axes and (once stage params
    # touch them) 'pipe'; they stay *invariant* over 'model' because every
    # block ends in a psum(MODEL).  Pre-cast the injected microbatches and the
    # zero-init carries to exactly that set so the scan types close.
    vary_axes = tuple(sorted(
        set(jax.typeof(x_micro).vma or ()) | {PIPE}))
    x_micro = lax.pcast(
        x_micro, tuple(a for a in vary_axes
                       if a not in (jax.typeof(x_micro).vma or ())),
        to="varying")
    buf0 = lax.pcast(jnp.zeros(mb_shape, cfg.dtype), vary_axes, to="varying")
    outs0 = lax.pcast(jnp.zeros((n_micro,) + mb_shape, cfg.dtype),
                      vary_axes, to="varying")
    stat0 = lax.pcast(jnp.zeros((), jnp.float32), vary_axes, to="varying")
    (_, outputs, drop, tot, auxs), _ = lax.scan(
        tick, (buf0, outs0, stat0, stat0, stat0), jnp.arange(n_ticks))
    # broadcast last stage's outputs to every stage (head/loss replicated)
    outputs = lax.psum(
        jnp.where(stage == n_stages - 1, outputs,
                  jnp.zeros_like(outputs)), PIPE)
    return outputs, (drop, tot, auxs)


def _loss_fn(cfg: MegatronConfig, params, tokens, targets, mask):
    """Global-mean causal LM loss on local shards. Inside shard_map.

    tokens/targets/mask: [b_loc, s_loc] int32 / int32 / f32.
    """
    b_loc, s_loc = tokens.shape
    n_micro = cfg.n_microbatches
    emb = params["embed"]
    x = jnp.take(emb, tokens, axis=0).astype(cfg.dtype)   # [b, s, D]
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq)

    mb = b_loc // n_micro
    x_micro = x.reshape(n_micro, mb, s_loc, cfg.d_model)
    y, (drop, tot, auxs) = _pipeline(cfg, params, x_micro, cos, sin)
    y = y.reshape(b_loc, s_loc, cfg.d_model)

    y = _rms(y, params["ln_f"])
    logits = jnp.einsum("bsd,vd->bsv", y.astype(jnp.float32),
                        emb.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, -1)
    true_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    local_sum = jnp.sum((lse - true_logit) * mask)
    total = lax.psum(jnp.sum(mask), (DATA, SEQ))
    loss = lax.psum(local_sum, (DATA, SEQ)) / jnp.maximum(total, 1.0)
    # per-(layer, microbatch) aux values are GLOBAL (psummed over
    # data/seq/model inside the MoE op), so every data/seq shard
    # accumulated the same sums: pmean is the value-preserving demotion,
    # psum would multiply by the shard count.  psum(PIPE) sums the stages'
    # disjoint layer contributions.
    aux_mean = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        aux_mean = lax.pmean(lax.psum(auxs, PIPE), (DATA, SEQ)) \
            / (cfg.n_layers * n_micro)
        loss = loss + cfg.moe_aux_weight * aux_mean
    aux = (lax.psum(drop, (DATA, SEQ, PIPE)),
           lax.psum(tot, (DATA, SEQ, PIPE)), aux_mean)
    return loss, aux


# ---------------------------------------------------------------------------
# the 1F1B schedule (explicit-VJP pipeline, inside shard_map)
# ---------------------------------------------------------------------------

def n_pipeline_ticks(cfg: MegatronConfig) -> int:
    """Combined fwd+bwd tick count of the (interleaved) 1F1B scan.

    General formula for v = ``virtual_stages`` chunks per device: the last
    microbatch's chunk-0 backward lands at
    ``(vS-1) + (S-1) + g*vS + (v-1)S + j`` where ``(g, j) = divmod(M-1, S)``.
    v=1 reduces to the classic ``M + 2(S-1)``.
    """
    S, M, v = cfg.n_stages, cfg.n_microbatches, cfg.virtual_stages
    g, j = divmod(M - 1, S)
    return (v * S - 1) + (S - 1) + g * v * S + (v - 1) * S + j + 1


def bubble_fraction(cfg: MegatronConfig) -> float:
    """Idle TIME fraction of the segmented (interleaved) 1F1B schedule.

    The scan is split into three segments (see `_value_and_grad_1f1b`):
    ``vS-1`` forward-only warmup ticks (cost tf/v each), ``T - 2(vS-1)``
    two-lane steady ticks ((tf+tb)/v), and ``vS-1`` backward-only
    cooldown ticks (tb/v).  Useful work per device is ``M(tf+tb)``; the
    excess idle time is exactly ``(S-1)(tf+tb)/v`` when M is a multiple
    of S — **the Megatron interleaved-1F1B bubble bound** (v=1 reduces
    to the classic 1F1B ``(S-1)/(M+S-1)`` fraction).  The earlier
    two-lane lockstep scan paid (tf+tb)/v on every tick including warmup
    and cooldown, capping at ~S(v+1)/(2v) chunk-times of idle;
    segmenting removed that structural penalty without touching the
    per-tick math.

    The *fraction* is independent of the tf:tb ratio by construction:
    warmup and cooldown have equal tick counts, so their combined cost
    is ``(vS-1)(tf+tb)/v`` and the ``(tf+tb)`` factor cancels —
    ``1 - Mv / (T - (vS-1))``.

    Relative to the GPipe path (`_loss_fn`): GPipe's scan runs M + S - 1
    forward ticks and lets autodiff replay them backward; its peak memory
    holds all M microbatch activations, while this schedule saves only
    ``min(k_span, 2vS-1)`` chunk inputs (k_span = M*v when M % S == 0)
    and needs no cross-stage broadcast.
    """
    S, m, v = cfg.n_stages, cfg.n_microbatches, cfg.virtual_stages
    return 1.0 - m * v / (n_pipeline_ticks(cfg) - (v * S - 1))


def _vary(x, axes):
    """pcast ``x`` to additionally vary over ``axes`` (no-op where it does)."""
    have = jax.typeof(x).vma or ()
    add = tuple(a for a in axes if a not in have)
    return lax.pcast(x, add, to="varying") if add else x


def _head_loss(cfg, emb, ln_f, y, targets, mask, inv_total):
    """Vocab-parallel LM head: scaled loss-sum of one microbatch.

    The vocab dim is sharded over 'model' (Megatron-style vocab-parallel
    cross entropy): each tp shard computes logits for its V/tp slice, the
    logsumexp and true-logit gather are combined with one scalar-per-token
    psum('model') each — the full [.., V] logits never materialize per
    device when tp > 1.
    """
    v = cfg.vocab_size
    tp = lax.axis_size(MODEL)
    h = _rms(y, ln_f).astype(jnp.float32)
    if tp > 1 and v % tp == 0:
        v_loc = v // tp
        off = lax.axis_index(MODEL) * v_loc
        emb_slice = lax.dynamic_slice_in_dim(emb, off, v_loc, 0)
        logits = jnp.einsum("bsd,vd->bsv", h, emb_slice.astype(jnp.float32))
        mx = lax.pmax(lax.stop_gradient(jnp.max(logits, -1)), MODEL)
        se = lax.psum(jnp.sum(jnp.exp(logits - mx[..., None]), -1), MODEL)
        lse = mx + jnp.log(se)
        in_range = (targets >= off) & (targets < off + v_loc)
        idx = jnp.clip(targets - off, 0, v_loc - 1)
        true_logit = lax.psum(
            jnp.where(in_range,
                      jnp.take_along_axis(logits, idx[..., None], -1)[..., 0],
                      0.0), MODEL)
    else:
        logits = jnp.einsum("bsd,vd->bsv", h, emb.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, -1)
        true_logit = jnp.take_along_axis(
            logits, targets[..., None], -1)[..., 0]
    loss = jnp.sum((lse - true_logit) * mask) * inv_total
    if MODEL in (jax.typeof(loss).vma or ()):
        # replicated-head branch: every tp shard computed the same value;
        # pmean is a value-preserving demotion to MODEL-unvarying, keeping
        # the scan carry types identical across both branches
        loss = lax.pmean(loss, MODEL)
    return loss


def _value_and_grad_1f1b(cfg: MegatronConfig, params, tokens, targets, mask):
    """(loss, grads) via an explicit (interleaved) 1F1B schedule.  Inside
    shard_map.

    Three ``lax.scan`` segments totalling :func:`n_pipeline_ticks` ticks:
    a forward-only warmup (vS-1 ticks), a two-lane steady phase, and a
    backward-only cooldown (vS-1 ticks) — per steady tick, every device
    runs one forward *chunk* and one backward *chunk* (rematerialized
    ``jax.vjp``), where a chunk is ``layers_per_stage / virtual_stages`` of
    its layers.  Segmenting prunes the provably-idle lane from the ramp
    ticks, landing the schedule on the Megatron interleaved bubble bound
    ``(S-1)(tf+tb)/v`` (`bubble_fraction`).  With ``v = virtual_stages``
    chunks per device the model is
    a virtual pipeline of depth ``V = v*S`` whose hops always target the
    next/prev device on the 'pipe' ring (chunk c on device S-1 wraps to
    chunk c+1 on device 0), so the two ``ppermute``s per tick are unchanged
    from the plain schedule.  Forward index math at tick ``t`` on device
    ``s``: ``t' = t - s``, group ``g = t' // (vS)``, chunk
    ``c = (t' mod vS) // S``, microbatch ``m = g*S + (t' mod S)`` — v=1
    reduces to the classic ``m = t - s``.  The backward lane mirrors it
    shifted by ``(vS-1) + (S-1-s)``, so the last device backprops a
    microbatch's final chunk the same tick it finishes its forward — the
    1F1B steady state, at any v.

    Compared with autodiff through the GPipe scan (`_loss_fn`), this (a)
    caps live activations at ``min(k_span, 2vS-1)`` chunk *inputs* (remat
    recomputes the rest), (b) never psum-broadcasts stage outputs — only
    scalar loss + per-microbatch dy leave the last device, and (c) shards
    the head's vocab dim over 'model'.  SPMD lockstep means every device
    still *executes* the head each tick (results masked off-stage).

    Gradient reductions that fall out of VMA-typed autodiff in `_loss_fn`
    are explicit here: chunk/embed/ln_f cotangents are accumulated locally
    (params pcast varying) and psummed once after the scan.  The head and
    input-embedding cotangents share ONE [V, D] accumulator (the head's
    contribution is MODEL-sharded by the vocab-parallel head; the input
    side is pre-divided by tp so the single psum over all axes is exact).
    """
    S, M, v = cfg.n_stages, cfg.n_microbatches, cfg.virtual_stages
    if cfg.layers_per_stage % v:
        raise ValueError(f"virtual_stages={v} must divide "
                         f"layers_per_stage={cfg.layers_per_stage}")
    Lc = cfg.layers_per_stage // v           # layers per chunk
    b_loc, s_loc = tokens.shape
    mb = b_loc // M
    D = cfg.d_model
    stage = lax.axis_index(PIPE)
    tp = lax.axis_size(MODEL)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq)

    inv_total = 1.0 / jnp.maximum(
        lax.psum(jnp.sum(mask), (DATA, SEQ)), 1.0)
    tok_micro = _vary(tokens.reshape(M, mb, s_loc), (PIPE,))
    tgt_micro = _vary(targets.reshape(M, mb, s_loc), (PIPE,))
    msk_micro = _vary(mask.reshape(M, mb, s_loc), (PIPE,))

    # localized (per-device cotangent) copies of everything we differentiate
    p_stage = jax.tree.map(lambda a: _vary(a[0], (DATA, SEQ)),
                           params["blocks"])
    emb_v = _vary(params["embed"], (DATA, SEQ, PIPE, MODEL))
    lnf_v = _vary(params["ln_f"], (DATA, SEQ, PIPE))

    def chunk_params(c):
        return jax.tree.map(
            lambda a: lax.dynamic_slice_in_dim(a, c * Lc, Lc, 0), p_stage)

    def chunk_fn(p, x):
        """(activations, load-balance aux) of one chunk — the aux output is
        part of the differentiated function so the backward lane can inject
        its loss cotangent (``aux_cot``) through the same rematerialized
        vjp that produces dx/dw."""
        y, st = _stage_forward(cfg, p, x, cos, sin)
        return y, _vary(st[2], (PIPE,))

    # d(loss)/d(chunk aux output): the aux objective is the mean over all
    # (layer, microbatch) pairs, weighted by moe_aux_weight — each chunk's
    # aux is a plain sum term, so its cotangent is the constant norm
    aux_cot_w = (cfg.moe_aux_weight / (cfg.n_layers * M)
                 if cfg.n_experts else 0.0)

    perm_up = [(i, (i + 1) % S) for i in range(S)]
    perm_down = [(i, (i - 1) % S) for i in range(S)]
    # ring-buffer slots for saved chunk inputs, keyed by the dense fwd-order
    # index k = g*vS + cS + j.  With a partial last group (M % S != 0) k is
    # not dense, so the small-M cap is the k-range, not M*v.
    g_last, j_last = divmod(M - 1, S)
    k_span = g_last * v * S + (v - 1) * S + j_last + 1
    n_slots = min(k_span, 2 * v * S - 1)
    n_ticks = n_pipeline_ticks(cfg)

    act_axes = tuple(sorted(set(jax.typeof(tok_micro).vma or ())))
    zeros_act = lambda shape: _vary(jnp.zeros(shape, cfg.dtype), act_axes)
    carry0 = dict(
        buf_f=zeros_act((mb, s_loc, D)),
        buf_b=zeros_act((mb, s_loc, D)),
        x_saved=zeros_act((n_slots, mb, s_loc, D)),
        dw=jax.tree.map(lambda a: jnp.zeros_like(a, jnp.float32), p_stage),
        demb=jnp.zeros_like(emb_v, jnp.float32),
        dlnf=jnp.zeros_like(lnf_v, jnp.float32),
        loss=_vary(jnp.zeros((), jnp.float32), act_axes),
        drop=_vary(jnp.zeros((), jnp.float32), act_axes),
        tot=_vary(jnp.zeros((), jnp.float32), act_axes),
        auxs=_vary(jnp.zeros((), jnp.float32), act_axes),
    )

    def fwd_indices(t):
        """(active, chunk, microbatch, dense-order k) of this device's
        forward lane at tick t."""
        tp_ = t - stage
        g = jnp.floor_divide(tp_, v * S)
        w = jnp.mod(tp_, v * S)
        c = jnp.floor_divide(w, S)
        m = g * S + jnp.mod(w, S)
        active = (tp_ >= 0) & (m < M)
        return active, c, jnp.clip(m, 0, M - 1), jnp.maximum(tp_, 0)

    def bwd_indices(t):
        """Mirror of fwd_indices, shifted by (vS-1) + (S-1-stage); the
        chunk counter runs top-down (chunk = v-1 - c')."""
        tb = t - (v * S - 1) - (S - 1 - stage)
        g = jnp.floor_divide(tb, v * S)
        w = jnp.mod(tb, v * S)
        cprime = jnp.floor_divide(w, S)
        j = jnp.mod(w, S)
        m = g * S + j
        active = (tb >= 0) & (m < M)
        chunk = v - 1 - cprime
        # dense fwd-order index of the entry being backproped (its slot)
        k = g * (v * S) + chunk * S + j
        return active, chunk, jnp.clip(m, 0, M - 1), jnp.maximum(k, 0)

    def make_tick(do_fwd: bool, do_bwd: bool):
        """One scan body specialized (at trace time) to its schedule
        segment.  The two-lane lockstep body used to run for ALL ticks,
        paying forward+backward chunk cost even through the warmup
        (where every device's backward lane is provably idle: tb <=
        t-(vS-1) < 0) and the cooldown (symmetrically, no forward lane
        and no head anywhere).  Splitting the scan into fwd-only /
        two-lane / bwd-only segments removes exactly that waste: per-tick
        cost (tf+tb)/v only in the steady segment, tf/v in warmup, tb/v
        in cooldown — total bubble (S-1)(tf+tb)/v, the Megatron
        interleaved 1F1B bound (see `bubble_fraction`)."""

        def tick(carry, t):
            x_saved = carry["x_saved"]
            loss, demb, dlnf = carry["loss"], carry["demb"], carry["dlnf"]
            drop, tot, auxs = carry["drop"], carry["tot"], carry["auxs"]
            y = dy_head = None
            if do_fwd:
                # ---- forward lane: chunk c_f of microbatch m_f ----------
                f_active, c_f, m_idx, k_f = fwd_indices(t)
                tok_f = lax.dynamic_index_in_dim(tok_micro, m_idx, 0,
                                                 keepdims=False)
                inject = jnp.take(params["embed"], tok_f,
                                  axis=0).astype(cfg.dtype)
                x_in = jnp.where((stage == 0) & (c_f == 0), inject,
                                 carry["buf_f"])
                slot_f = jnp.mod(k_f, n_slots)
                old = lax.dynamic_index_in_dim(x_saved, slot_f, 0,
                                               keepdims=False)
                x_saved = lax.dynamic_update_index_in_dim(
                    x_saved, jnp.where(f_active, x_in, old), slot_f, 0)
                p_f = chunk_params(c_f)
                y, st = _stage_forward(cfg, p_f, x_in, cos, sin)
                drop = drop + jnp.where(f_active, st[0], 0.0)
                tot = tot + jnp.where(f_active, st[1], 0.0)
                auxs = auxs + jnp.where(f_active, st[2], 0.0)

            if do_fwd and do_bwd:
                # ---- head on the final chunk's output (last device) ----
                # only the steady segment needs it: the first head fires
                # at t = vS-1 (after warmup) and its dy is consumed by the
                # SAME tick's backward lane, never later
                tgt = lax.dynamic_index_in_dim(tgt_micro, m_idx, 0,
                                               keepdims=False)
                msk = lax.dynamic_index_in_dim(msk_micro, m_idx, 0,
                                               keepdims=False)
                loss_m, head_vjp = jax.vjp(
                    lambda e, lf, yy: _head_loss(cfg, e, lf, yy, tgt, msk,
                                                 inv_total),
                    emb_v, lnf_v, y)
                demb_m, dlnf_m, dy_head = head_vjp(
                    _vary(jnp.float32(1.0), jax.typeof(loss_m).vma or ()))
                head_active = (stage == S - 1) & (c_f == v - 1) & f_active
                loss = loss + jnp.where(head_active, loss_m, 0.0)
                demb = demb + jnp.where(head_active, demb_m, 0.0)
                dlnf = dlnf + jnp.where(head_active, dlnf_m, 0.0)

            dw, dx = carry["dw"], None
            if do_bwd:
                # ---- backward lane: chunk c_b of microbatch u_b ---------
                b_active, c_b, u_idx, k_b = bwd_indices(t)
                x_b = lax.dynamic_index_in_dim(
                    x_saved, jnp.mod(k_b, n_slots), 0, keepdims=False)
                dy = carry["buf_b"]
                if dy_head is not None:
                    dy = jnp.where((stage == S - 1) & (c_b == v - 1),
                                   dy_head, dy)
                p_b = chunk_params(c_b)
                (_, aux_b), chunk_vjp = jax.vjp(chunk_fn, p_b, x_b)
                # the aux-loss cotangent rides the same rematerialized
                # chunk vjp as the activation cotangent; inactive backward
                # lanes get zero
                aux_cot = jnp.where(b_active, jnp.float32(aux_cot_w), 0.0)
                dw_m, dx = chunk_vjp((dy, _vary(aux_cot,
                                                jax.typeof(aux_b).vma
                                                or ())))

                def acc_chunk(a, d):
                    cur = lax.dynamic_slice_in_dim(a, c_b * Lc, Lc, 0)
                    return lax.dynamic_update_slice_in_dim(
                        a, cur + jnp.where(b_active, d, 0.0), c_b * Lc, 0)

                dw = jax.tree.map(acc_chunk, dw, dw_m)
                # input-embedding cotangent (scatter-add), device 0 chunk
                # 0 only; pre-divided by tp so it can share the
                # MODEL-psummed accumulator
                tok_b = lax.dynamic_index_in_dim(tok_micro, u_idx, 0,
                                                 keepdims=False)
                _, embed_vjp = jax.vjp(
                    lambda e: jnp.take(e, tok_b, axis=0).astype(cfg.dtype),
                    emb_v)
                (demb_u,) = embed_vjp(_vary(dx, (MODEL,)))
                demb = demb + jnp.where(
                    b_active & (stage == 0) & (c_b == 0), demb_u / tp, 0.0)

            # ---- ring handoffs (only the lanes that ran) ---------------
            new_carry = dict(
                buf_f=lax.ppermute(y, PIPE, perm_up)
                if do_fwd else carry["buf_f"],
                buf_b=lax.ppermute(dx, PIPE, perm_down)
                if do_bwd else carry["buf_b"],
                x_saved=x_saved, dw=dw, demb=demb,
                dlnf=dlnf, loss=loss, drop=drop, tot=tot, auxs=auxs)
            return new_carry, None

        return tick

    # schedule segments: warmup [0, vS-1) has no backward anywhere
    # (tb = t-(vS-1)-(S-1-s) < 0 for every s), cooldown [fwd_end, T) has
    # no forward anywhere (every device past its last microbatch) and no
    # head (a head's dy is consumed the same tick it is produced) —
    # n_pipeline_ticks = fwd_end + (vS-1), so the segments partition it
    warm_end = v * S - 1
    fwd_end = n_ticks - warm_end
    carry = carry0
    if warm_end:
        carry, _ = lax.scan(make_tick(True, False), carry,
                            jnp.arange(0, warm_end))
    carry, _ = lax.scan(make_tick(True, True), carry,
                        jnp.arange(warm_end, fwd_end))
    if warm_end:
        carry, _ = lax.scan(make_tick(False, True), carry,
                            jnp.arange(fwd_end, n_ticks))

    # ---- combine cotangents into global-layout grads ---------------------
    demb = lax.psum(carry["demb"], (DATA, SEQ, PIPE, MODEL))
    dlnf = lax.psum(carry["dlnf"], (DATA, SEQ, PIPE))
    dblocks = jax.tree.map(lambda a: lax.psum(a, (DATA, SEQ))[None],
                           carry["dw"])
    loss = lax.psum(carry["loss"], (DATA, SEQ, PIPE))
    grads = {"embed": demb, "ln_f": dlnf, "blocks": dblocks}
    aux_mean = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        # per-(layer, microbatch) aux values are global sums (see
        # _loss_fn): pmean demotes, psum(PIPE) adds the stages' layers
        aux_mean = lax.pmean(lax.psum(carry["auxs"], PIPE), (DATA, SEQ)) \
            / (cfg.n_layers * M)
        loss = loss + cfg.moe_aux_weight * aux_mean
    aux = (lax.psum(carry["drop"], (DATA, SEQ, PIPE)),
           lax.psum(carry["tot"], (DATA, SEQ, PIPE)), aux_mean)
    return loss, grads, aux


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def opt_state_specs(cfg: MegatronConfig, optimizer):
    """PartitionSpecs for the optimizer state: param-like leaves (momentum,
    second moments) shard exactly like their parameters; scalar bookkeeping
    (step counts) is replicated."""
    import optax
    specs = param_specs(cfg)
    shapes = jax.eval_shape(partial(init_params, cfg), jax.random.PRNGKey(0))
    state_shape = jax.eval_shape(optimizer.init, shapes)
    return optax.tree_map_params(
        optimizer, lambda _, s: s, state_shape, specs,
        transform_non_params=lambda _: P())


def make_megatron_train_step(cfg: MegatronConfig, mesh: Mesh, optimizer):
    """Compiled 4D-parallel train step ``(params, opt_state, batch) -> ...``.

    ``batch``: dict of global arrays — 'tokens'/'targets' int32
    [global_batch, global_seq], 'mask' float32 — sharded
    P('data', 'seq') by :func:`shard_lm_batch`.  Gradient reductions over
    every axis fall out of VMA-typed autodiff: params enter unvarying, the
    loss psums make them exact (no hand-written grad allreduce to get wrong).
    """
    if cfg.n_stages != mesh.shape[PIPE]:
        raise ValueError(
            f"cfg.n_stages={cfg.n_stages} must equal mesh 'pipe' size "
            f"{mesh.shape[PIPE]}")
    specs = param_specs(cfg)
    o_specs = opt_state_specs(cfg, optimizer)

    if cfg.schedule not in ("1f1b", "gpipe"):
        raise ValueError(f"unknown pipeline schedule {cfg.schedule!r}")
    if cfg.schedule == "gpipe" and cfg.virtual_stages != 1:
        raise ValueError("virtual_stages (interleaved schedule) requires "
                         "schedule='1f1b'")
    def step(params, opt_state, tokens, targets, mask):
        if cfg.schedule == "1f1b":
            loss, grads, aux = _value_and_grad_1f1b(cfg, params, tokens,
                                                    targets, mask)
        else:
            (loss, aux), grads = jax.value_and_grad(
                partial(_loss_fn, cfg), has_aux=True)(
                    params, tokens, targets, mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        metrics = {}
        if cfg.n_experts:
            drop, tot, aux_mean = aux
            metrics["moe_aux_loss"] = aux_mean
            if cfg.moe_dispatch == "routed":
                metrics["moe_dropped_frac"] = drop / jnp.maximum(tot, 1.0)
        return params, opt_state, loss, metrics

    metric_spec = {}
    if cfg.n_experts:
        metric_spec["moe_aux_loss"] = P()
        if cfg.moe_dispatch == "routed":
            metric_spec["moe_dropped_frac"] = P()
    batch_spec = P(DATA, SEQ)
    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(specs, o_specs, batch_spec, batch_spec, batch_spec),
        out_specs=(specs, o_specs, P(), metric_spec),
    )
    return jax.jit(mapped, donate_argnums=(0, 1))


def make_megatron_eval_step(cfg: MegatronConfig, mesh: Mesh):
    """Compiled 4D-parallel eval step: forward + metrics, no optimizer.

    ``(params, tokens, targets, mask) -> {'loss', 'accuracy', 'n_tokens'}``
    with the same ``P('data','seq')`` batch placement as training
    (:func:`shard_lm_batch`).  Parity target: every reference script
    evaluates — restore-then-evaluate (reference
    tensorflow2/mnist_single.py:88-92) and the allreduced multi-node
    evaluator (reference chainer/train_mnist_multi.py:101-104); this is the
    4D engine's equivalent, so validation never needs an optimizer update
    (the train step donates params/opt_state, which makes "step but ignore
    the update" unusable for eval).

    Runs the GPipe forward scan regardless of ``cfg.schedule`` — with no
    backward pass 1F1B's interleaving buys nothing, and the forward-only
    scan holds no activation stash.  The LM head is vocab-parallel like
    training's (`_head_loss`): per-shard logits over the V/tp slice,
    logsumexp/true-logit/argmax combined with one psum/pmax/pmin('model')
    each, so full [.., V] logits never materialize when tp > 1.  Loss and
    accuracy are masked global sums over ('data','seq') divided by the
    psummed mask total — ragged tails (mask=0 padding) are exact, matching
    the DP engines' sum-synced metrics.  The eval loss is the plain LM
    cross entropy: the MoE balance aux is a *training* regularizer and is
    deliberately not added to validation loss.
    """
    if cfg.n_stages != mesh.shape[PIPE]:
        raise ValueError(
            f"cfg.n_stages={cfg.n_stages} must equal mesh 'pipe' size "
            f"{mesh.shape[PIPE]}")
    specs = param_specs(cfg)
    batch_spec = P(DATA, SEQ)

    def eval_fn(params, tokens, targets, mask):
        b_loc, s_loc = tokens.shape
        n_micro = cfg.n_microbatches
        emb = params["embed"]
        x = jnp.take(emb, tokens, axis=0).astype(cfg.dtype)
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq)
        x_micro = x.reshape(n_micro, b_loc // n_micro, s_loc, cfg.d_model)
        y, _ = _pipeline(cfg, params, x_micro, cos, sin)
        y = y.reshape(b_loc, s_loc, cfg.d_model)
        h = _rms(y, params["ln_f"]).astype(jnp.float32)

        v = cfg.vocab_size
        tp = lax.axis_size(MODEL)
        if tp > 1 and v % tp == 0:
            v_loc = v // tp
            off = lax.axis_index(MODEL) * v_loc
            emb_slice = lax.dynamic_slice_in_dim(emb, off, v_loc, 0)
            logits = jnp.einsum("bsd,vd->bsv", h,
                                emb_slice.astype(jnp.float32))
            loc_max = jnp.max(logits, -1)
            mx = lax.pmax(loc_max, MODEL)
            se = lax.psum(jnp.sum(jnp.exp(logits - mx[..., None]), -1),
                          MODEL)
            lse = mx + jnp.log(se)
            in_range = (targets >= off) & (targets < off + v_loc)
            idx = jnp.clip(targets - off, 0, v_loc - 1)
            true_logit = lax.psum(
                jnp.where(in_range,
                          jnp.take_along_axis(logits, idx[..., None],
                                              -1)[..., 0],
                          0.0), MODEL)
            # global argmax with jnp.argmax's first-occurrence tie-break:
            # shards whose local max hits the global max bid their local
            # argmax (+vocab offset); everyone else bids the out-of-range
            # sentinel V; pmin picks the lowest winning index
            loc_arg = jnp.argmax(logits, -1).astype(jnp.int32) + off
            pred = lax.pmin(jnp.where(loc_max == mx, loc_arg, v), MODEL)
        else:
            logits = jnp.einsum("bsd,vd->bsv", h, emb.astype(jnp.float32))
            lse = jax.nn.logsumexp(logits, -1)
            true_logit = jnp.take_along_axis(
                logits, targets[..., None], -1)[..., 0]
            pred = jnp.argmax(logits, -1).astype(jnp.int32)

        loss_sum = lax.psum(jnp.sum((lse - true_logit) * mask), (DATA, SEQ))
        correct = lax.psum(
            jnp.sum((pred == targets).astype(jnp.float32) * mask),
            (DATA, SEQ))
        count = lax.psum(jnp.sum(mask), (DATA, SEQ))
        denom = jnp.maximum(count, 1.0)
        out = {"loss": loss_sum / denom, "accuracy": correct / denom,
               "n_tokens": count}
        # the replicated-head branch leaves the scalars MODEL-varying in
        # vma type only (every shard computed the same value); pmean is the
        # value-preserving demotion so out_specs P() is accepted
        return {k: lax.pmean(s, MODEL)
                if MODEL in (jax.typeof(s).vma or ()) else s
                for k, s in out.items()}

    mapped = jax.shard_map(
        eval_fn, mesh=mesh,
        in_specs=(specs, batch_spec, batch_spec, batch_spec),
        out_specs={"loss": P(), "accuracy": P(), "n_tokens": P()},
    )
    jitted = jax.jit(mapped)   # no donation: params are reused for training

    def eval_step(params, tokens, targets, mask):
        # validate the microbatch split HERE: inside shard_map tracing the
        # same mistake surfaces as an opaque reshape error deep in the
        # pipeline scan, far from the caller's batch-size choice
        n_data = mesh.shape[DATA]
        b_glob = tokens.shape[0]
        b_loc = b_glob // n_data
        if b_glob % n_data or b_loc % cfg.n_microbatches:
            raise ValueError(
                f"eval batch size {b_glob} is not splittable: the local "
                f"batch b_loc = {b_glob} / {n_data} ('data' mesh axis) = "
                f"{b_loc} must satisfy b_loc % n_microbatches == 0 "
                f"(n_microbatches={cfg.n_microbatches}); use a global "
                f"batch that is a multiple of "
                f"{n_data * cfg.n_microbatches}")
        return jitted(params, tokens, targets, mask)

    return eval_step


def init_optimizer(cfg: MegatronConfig, mesh: Mesh, optimizer, params):
    """Optimizer state placed with param-aligned shardings."""
    state = optimizer.init(params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        state, opt_state_specs(cfg, optimizer))


def abstract_state(cfg: MegatronConfig, mesh: Mesh, optimizer):
    """Sharded abstract ``(params, opt_state)`` — the orbax restore target.

    Each leaf is a ShapeDtypeStruct carrying the NamedSharding from
    :func:`param_specs` / :func:`opt_state_specs`, so a snapshot restores
    directly into the 4D layout (every host reads only its shards) without
    materializing the global arrays anywhere.  This is what makes the 4D
    path restartable: checkpoint/resume at scale needs no gather step.
    Mirrors the reference's full trainer-state resume
    (chainer/train_mnist.py:120-122) for the megatron engine.
    """
    p_shapes = jax.eval_shape(partial(init_params, cfg),
                              jax.random.PRNGKey(0))
    o_shapes = jax.eval_shape(optimizer.init, p_shapes)

    def to_sds(leaf, spec):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))

    return (jax.tree.map(to_sds, p_shapes, param_specs(cfg)),
            jax.tree.map(to_sds, o_shapes,
                         opt_state_specs(cfg, optimizer)))


def shard_lm_batch(mesh: Mesh, batch: dict) -> dict:
    """Place tokens/targets/mask as [batch@'data', seq@'seq'] global arrays.

    When the mesh has a 'seq' axis > 1 the sequence dim is permuted into the
    **zigzag order** first (dtdl_tpu/parallel/sequence.py zigzag_order) —
    the layout contract of the 4D step's causal ring attention.  The LM loss
    is a masked mean over positions, so the permutation changes nothing
    observable; callers that need position-ordered logits apply
    ``zigzag_inverse``.  (Multi-host note: the permutation is applied to
    each process's local view, which is exact as long as the 'seq' axis
    does not span processes — the standard placement, dp over DCN —
    enforced below.)
    """
    n_sp = mesh.shape[SEQ]
    if n_sp > 1:
        if jax.process_count() > 1:
            # a process-spanning 'seq' axis would make the local-view
            # permutation silently wrong — refuse instead
            seq_axis = mesh.axis_names.index(SEQ)
            rows = np.moveaxis(mesh.devices, seq_axis, -1).reshape(-1, n_sp)
            for row in rows:
                if len({d.process_index for d in row}) != 1:
                    raise ValueError(
                        "zigzag shard_lm_batch requires the 'seq' mesh axis "
                        "to be process-local; lay 'data' over DCN instead")
        order = zigzag_order(n_sp, next(iter(batch.values())).shape[1])
        # audit: ok[host-sync-asarray] host batch reorder before device_put — input is host data by contract
        batch = {k: np.asarray(v)[:, order] for k, v in batch.items()}
    sharding = NamedSharding(mesh, P(DATA, SEQ))
    if jax.process_count() == 1:
        return {k: jax.device_put(v, sharding) for k, v in batch.items()}
    return {k: jax.make_array_from_process_local_data(sharding, v)
            for k, v in batch.items()}


def to_flax_params(cfg: MegatronConfig, params: dict) -> dict:
    """Convert the 4D engine's stacked parameter tree into the flax
    :class:`~dtdl_tpu.models.transformer.TransformerLM` tree — the
    serving bridge: train on the megatron engine, restore a snapshot,
    convert, and decode with ``models.generate`` (single-device,
    DP-batch-sharded, or tensor-parallel — generate propagates whatever
    sharding the converted params carry).

    The stacked ``blocks`` leaves are [n_stages, layers_per_stage, ...];
    execution order is the (interleaved) virtual pipeline's — virtual
    stage ``u = c*S + st`` runs device st's chunk-c rows — so flax
    ``block_j`` takes row ``order[j]``.  Attention kernels reshape
    [D, H*hd] -> [D, H, hd] (flax DenseGeneral layout); both engines
    share the rope/RMSNorm/SwiGLU ops, so the converted model computes
    the identical function (pinned by test).  MoE configs map too
    (router/wi/wg/wo shapes coincide) but require the flax model built
    with ``moe_every=1`` — the megatron engine puts an MoE in *every*
    block.  Pass host (or fully-addressable) arrays; use
    ``jax.device_get`` on a sharded state first.
    """
    S, Lc_total, v = cfg.n_stages, cfg.layers_per_stage, cfg.virtual_stages
    H, hd, D = cfg.n_heads, cfg.head_dim, cfg.d_model
    if Lc_total % v:
        # same guard as the engine (_value_and_grad_1f1b): a silent
        # truncated conversion would fail far away with missing blocks
        raise ValueError(f"virtual_stages={v} must divide "
                         f"layers_per_stage={Lc_total}")
    Lc = Lc_total // v
    order = [(u % S, (u // S) * Lc + i)
             for u in range(v * S) for i in range(Lc)]
    blocks = params["blocks"]
    out = {"embed": params["embed"],
           "ln_f": {"scale": params["ln_f"]}}
    for j, (st, li) in enumerate(order):
        p = {k: a[st, li] for k, a in blocks.items()}
        blk = {
            "ln_attn": {"scale": p["ln_attn"]},
            "ln_mlp": {"scale": p["ln_mlp"]},
            "attn": {
                "q": {"kernel": p["wq"].reshape(D, H, hd)},
                "k": {"kernel": p["wk"].reshape(D, H, hd)},
                "v": {"kernel": p["wv"].reshape(D, H, hd)},
                "out": {"kernel": p["wo"].reshape(H, hd, D)},
            },
        }
        if cfg.n_experts:
            blk["moe"] = {"router": {"kernel": p["router"]},
                          "wi": p["wi"], "wg": p["wg"],
                          "wo": p["wo_mlp"]}
        else:
            blk["mlp"] = {"wi": {"kernel": p["wi"]},
                          "wg": {"kernel": p["wg"]},
                          "wo": {"kernel": p["wo_mlp"]}}
        out[f"block_{j}"] = blk
    return out


def to_flax_model(cfg: MegatronConfig, **overrides):
    """Flax :class:`~dtdl_tpu.models.transformer.TransformerLM` matching
    ``cfg`` — the model half of the serving bridge (:func:`to_flax_params`
    is the weights half).

    This is THE single place that maps MegatronConfig fields onto the flax
    model, so a new config field (say a future ``moe_group_size``) gets
    wired here once instead of silently drifting in every caller that
    hand-builds the serving model.  Bridge-mandated settings: ``moe_every=1``
    (the 4D engine puts an MoE in *every* block), the config's OWN
    ``moe_dispatch`` (decode keeps the TRAINED routing semantics — a
    dense-dispatch-trained MoE must not serve through capacity routing),
    and ``attn_impl='dense'`` / f32 as serving-safe defaults.  ``overrides``
    win last — e.g. ``max_seq=...`` to extend the rope table for decode.
    """
    from dtdl_tpu.models.transformer import TransformerLM
    kw = dict(
        vocab_size=cfg.vocab_size,
        d_model=cfg.d_model,
        n_layers=cfg.n_layers,
        n_heads=cfg.n_heads,
        d_ff=cfg.d_ff,
        max_seq=cfg.max_seq,
        n_experts=cfg.n_experts,
        moe_every=1,
        moe_dispatch=cfg.moe_dispatch if cfg.n_experts else "dense",
        capacity_factor=cfg.capacity_factor,
        moe_top_k=cfg.moe_top_k,
        attn_impl="dense",
        dtype=jnp.float32,
    )
    kw.update(overrides)
    return TransformerLM(**kw)


def place_params(mesh: Mesh, cfg: MegatronConfig, params: dict) -> dict:
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs)


def serve_engine(cfg: MegatronConfig, params: dict, mesh: Mesh = None,
                 n_slots: int = 8, buckets=None, page_size: int = 0,
                 n_pages: int = None, quantize_weights: bool = False,
                 kv_dtype=None, kv_pool_bytes: int = None, rules=None,
                 **overrides):
    """Train on the 4D engine, serve through dtdl_tpu.serve — the full
    bridge in one call: :func:`to_flax_model` (geometry) +
    :func:`to_flax_params` (weights) + an
    :class:`~dtdl_tpu.serve.InferenceEngine` around them.

    With ``mesh`` alone, the converted params are placed **replicated**
    on it (``NamedSharding(mesh, P())``) and the engine's jitted
    prefill/decode programs run under GSPMD on that mesh — the same
    pjit machinery the training step used, so a training pod flips to
    serving without a new runtime.  Replication is the right default at
    serving batch sizes: decode is HBM-bandwidth-bound on the weights
    (SCALING.md "Serving latency model"), and every chip holding all
    weights turns the mesh into throughput-parallel decode capacity.

    ``mesh`` plus ``rules`` (e.g. ``'tp'``) serves **tensor-parallel
    proper** (round 19): this function is now a thin caller — the
    engine itself shards params and the KV arena via the GSPMD presets
    in parallel/tensor.py (``InferenceEngine(mesh=, rules=)``), so a
    model too big to replicate serves with 1/tp of the weight and KV
    bytes per chip, and a serving engine no longer needs the megatron
    training mesh at all.

    ``params`` may be the live sharded training state (``device_get`` is
    applied before conversion).  ``overrides`` reach
    :func:`to_flax_model` — e.g. ``max_seq=4096`` to serve longer than
    the trained context.

    The engine-geometry kwargs pass straight through to
    :class:`~dtdl_tpu.serve.InferenceEngine`: ``page_size``/``n_pages``/
    ``kv_pool_bytes`` build the block-paged arena (prefix caching is
    scheduler policy on top), ``quantize_weights``/``kv_dtype`` the int8
    serving variants (dtdl_tpu/quant) — quantization happens AFTER the
    4D→flax conversion, so a bf16/f32 training snapshot serves int8
    without retraining.
    """
    from dtdl_tpu.serve import InferenceEngine

    if rules is not None and mesh is None:
        # silently dropping the requested sharding would surface as an
        # OOM (or one-chip serving) far from the misconfiguration
        raise ValueError(f"rules={rules!r} requires mesh=: "
                         f"tensor-parallel serving needs the mesh the "
                         f"shards land on")
    model = to_flax_model(cfg, **overrides)
    # audit: ok[host-sync-get] to_flax_model is the cold train->serve bridge, not a step path
    fparams = to_flax_params(cfg, jax.device_get(params))
    if mesh is not None and rules is None:
        # replicated placement (the throughput-parallel default); the
        # tensor-parallel path below lets the ENGINE place the shards
        fparams = jax.tree.map(
            lambda p: jax.device_put(p, NamedSharding(mesh, P())), fparams)
    return InferenceEngine(model, fparams, n_slots=n_slots,
                           buckets=buckets, page_size=page_size,
                           n_pages=n_pages,
                           quantize_weights=quantize_weights,
                           kv_dtype=kv_dtype,
                           kv_pool_bytes=kv_pool_bytes,
                           mesh=mesh if rules is not None else None,
                           rules=rules if rules is not None else "tp")
