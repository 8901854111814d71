"""The Qwen3-Next family (``"model_type": "qwen3_next"``): the five names
``lib/modules.py`` lists, and the plain reference behind them.  Imports
nothing of the program.

**The equations** (what the reference computes; the program may reorder,
never omit).  Block ``i`` of ``n``: ``h = x + Mixer_i(norm(x))``, ``y = h +
MoE(norm(h))``; ``Mixer_i`` is gated attention where ``(i + 1) %
full_attention_interval == 0`` and Gated DeltaNet elsewhere; ``norm`` is the
zero-centred RMSNorm ``x / sqrt(mean(x^2) + eps) * (1 + w)``; the same norm
closes the stack, then an untied head ``[vocab, hidden]`` and the mean
next-token cross entropy.

*Gated DeltaNet* (arXiv:2412.06464): ``in_qkvz`` gives each key head its q,
k and, for the value heads it serves, v and z; ``in_ba`` their b and a.  q,
k, v pass a causal depthwise conv (no bias) and SiLU; q and k are
L2-normalised over the head, q scaled by ``key_dim ** -0.5``; ``beta =
sigmoid(b)``; ``g = -exp(A_log) * softplus(a + dt_bias)``.  A value head,
with ``S = 0`` at the row's start:

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;
    o_t = S^T q_t

**token by token** here (``lax.scan`` over positions, checkpointed in blocks
of :data:`_SCAN_BLOCK` positions so that only the states at block
boundaries are kept), where the program computes the chunkwise WY form: two
derivations of one equation.  Then ``RMSNorm(o_t) * w * silu(z_t)`` over
the value head, heads concatenated, ``out``.

*Gated attention*: q is ``[heads, 2 * head_dim]`` a token, split a head
into query and gate; zero-centred RMSNorm over the head on q and k; rotary
(rotate-half) on the head's first ``partial_rotary_factor`` dims; causal
softmax attention, each K/V head serving ``heads / kv_heads`` query heads,
in blocks of :data:`_QUERY_BLOCK` queries; ``out(attn * sigmoid(gate))``.

*Experts*: router logits and softmax over all ``router_num_experts`` in
float32, the ``num_experts_per_tok`` largest, divided by their sum; the
routed output is the sum over the chosen experts **that are held here**
(``first_expert_held ... + num_experts``), a plain loop over the held
experts with masks, so nothing is ever dropped; plus ``sigmoid(x w_s) *
SwiGLU(x)`` of the shared expert.  The loss is the plain cross entropy: no
balance term (the source gives no coefficient).

``precision`` as in ``lib/reference.py``: every matmul's operands rounded
(``"bf16"``, ``"fp8"`` the control), the delta rule's q, k, v too.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from lib import reference

_SCAN_BLOCK = 64        # positions between two kept states of the recurrence
_QUERY_BLOCK = 512      # queries whose f32 scores are held at once


# ---------------------------------------------------------------------------
# the program's keywords, and how its leaves are drawn
# ---------------------------------------------------------------------------

def _layer_kinds(cfg: dict) -> tuple:
    period = cfg["full_attention_interval"]
    return tuple("full" if (i + 1) % period == 0 else "linear"
                 for i in range(cfg["num_hidden_layers"]))


def model_kwargs(cfg: dict, remat: bool) -> dict:
    """The program's names for the configuration file's (HF) keys."""
    if cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1 \
            or cfg["tie_word_embeddings"] or not cfg["norm_topk_prob"]:
        raise ValueError("the qwen3_next family routes every layer, unties "
                         "the head and normalises the chosen probabilities")
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], attn_impl="flash",
        remat=remat, layer_kinds=_layer_kinds(cfg),
        n_kv_heads=cfg["num_key_value_heads"],
        attn_head_dim=cfg["head_dim"],
        rope_dims=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_theta"]), qk_norm=True, attn_gate=True,
        norm_zero_centered=True,
        gdn_key_heads=cfg["linear_num_key_heads"],
        gdn_value_heads=cfg["linear_num_value_heads"],
        gdn_key_dim=cfg["linear_key_head_dim"],
        gdn_value_dim=cfg["linear_value_head_dim"],
        gdn_conv=cfg["linear_conv_kernel_dim"],
        n_experts=cfg["num_experts"], moe_every=1, moe_dispatch="held",
        moe_router_width=cfg["router_num_experts"],
        moe_first_expert=cfg["first_expert_held"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_shared_d_ff=cfg["shared_expert_intermediate_size"],
        tie_embeddings=False)


def leaf_moments(path: str, shape) -> tuple[float, float]:
    """``(mean, std)`` of a leaf's normal draw (each listed under the
    configuration's ``assumed``): kernels ``1 / sqrt(fan_in)``; zero-centred
    norm weights around 0, the delta rule's gated norm around 1; conv taps
    so that the conv's output has its input's scale; ``dt_bias`` around 1;
    ``A_log`` so that the median decay ``exp(g)`` is about 0.99 a token (a
    state that outlives several chunks)."""
    name = path.split("/")
    leaf = name[-1]
    if leaf == "scale":
        return (1.0, 0.1) if name[-3:-1] == ["gdn", "norm"] else (0.0, 0.1)
    if leaf in ("embed", "head"):
        return 0.0, 0.02
    if leaf == "A_log":
        # g = -exp(A_log) softplus(a + dt_bias), a ~ N(0, 1): the median
        # softplus is 1.31, so exp(-4.9) * 1.31 = 0.0098 and exp(g) = 0.990
        return -4.9, 0.3
    if leaf == "dt_bias":
        return 1.0, 0.1
    if leaf in ("wi", "wg", "wo") and name[-2] == "experts":
        return 0.0, 1.0 / math.sqrt(shape[1])       # [held, in, out]
    if leaf == "kernel":
        if name[-2] == "conv":
            return 0.0, 1.0 / math.sqrt(shape[0])   # [taps, channels]
        # the output projections contract (heads, head size); all others
        # contract their first axis
        fan_in = shape[0] * shape[1] if name[-2] == "out" else shape[0]
        return 0.0, 1.0 / math.sqrt(fan_in)
    raise ValueError(f"no rule to generate parameter leaf {path!r}")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rounded(x, precision):
    """``x`` rounded as a matmul operand of ``precision`` would be, with the
    gradient passed straight through."""
    if precision == "f32":
        return x
    return x + lax.stop_gradient(reference._round_operand(x, precision) - x)


def _norm(x, w, eps):
    """Zero-centred RMSNorm over the last axis."""
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _partial_rope(x, dims, theta):
    """x [S, H, D]: rotate-half on the first ``dims``, positions 0..S-1."""
    return jnp.concatenate(
        [reference._rope(x[..., :dims], theta), x[..., dims:]], axis=-1)


def _delta_rule(q, k, v, g, beta):
    """The recurrence, token by token.  q, k [S, H, Dk], v [S, H, Dv], g,
    beta [S, H]; returns o [S, H, Dv].  Blocks of ``_SCAN_BLOCK`` positions
    are checkpointed: the backward pass keeps one state a block."""
    s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % _SCAN_BLOCK

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, _SCAN_BLOCK) + x.shape[1:])

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, None, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", state, k_t, precision="highest"))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision="highest")

    @jax.checkpoint
    def block(state, xs):
        return lax.scan(token, state, xs)

    # padded positions have beta = 0 and g = 0: they change nothing
    _, o = lax.scan(block, jnp.zeros((h, dk, dv), jnp.float32),
                    tuple(blocks(x) for x in (q, k, v, g, beta)))
    return o.reshape(-1, h, dv)[:s]


def _gated_delta_net(x, p, cfg, precision):
    mm = functools.partial(reference._mm, precision=precision)
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r, s = hv // hk, x.shape[0]
    qkvz = mm("sd,dhe->she", x, p["gdn/in_qkvz/kernel"])
    ba = mm("sd,dhe->she", x, p["gdn/in_ba/kernel"])
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    mixed = jnp.concatenate([t.reshape(s, -1) for t in (q, k, v)], axis=-1)
    taps = p["gdn/conv/kernel"]
    width = taps.shape[0]
    padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(taps[j] * padded[j:j + s] for j in range(width)))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
    q, k = q.reshape(s, hk, dk), k.reshape(s, hk, dk)

    def l2(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) * dk ** -0.5, r, axis=1)
    k = jnp.repeat(l2(k), r, axis=1)
    v = v.reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(s, hv))
    g = -jnp.exp(p["gdn/A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(s, hv) + p["gdn/dt_bias"])
    o = _delta_rule(*(_rounded(t, precision) for t in (q, k, v)), g, beta)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + cfg["rms_norm_eps"]) * p["gdn/norm/scale"]
    o = o * jax.nn.silu(z.reshape(s, hv, dv))
    return mm("she,hed->sd", o, p["gdn/out/kernel"])


def _gated_attention(x, p, cfg, precision):
    mm = functools.partial(reference._mm, precision=precision)
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dims = int(d * cfg["partial_rotary_factor"])
    s = x.shape[0]
    q = mm("sd,dhe->she", x, p["attn/q/kernel"])
    q, gate = q[..., :d], q[..., d:]
    k = mm("sd,dhe->she", x, p["attn/k/kernel"])
    v = mm("sd,dhe->she", x, p["attn/v/kernel"])
    q = _partial_rope(_norm(q, p["attn/q_norm/scale"], eps), dims,
                      cfg["rope_theta"])
    k = _partial_rope(_norm(k, p["attn/k_norm/scale"], eps), dims,
                      cfg["rope_theta"])
    pad = -s % _QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, _QUERY_BLOCK, kv, h // kv, d)
    first = jnp.arange(qb.shape[0]) * _QUERY_BLOCK

    @jax.checkpoint
    def queries(args):
        q_blk, start = args                     # [Q, kv, group, d]
        scores = mm("qhge,khe->hgqk", q_blk, k) / jnp.sqrt(jnp.float32(d))
        seen = (start + jnp.arange(_QUERY_BLOCK))[:, None] \
            >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("hgqk,khe->qhge", probs, v)

    o = lax.map(queries, (qb, first)).reshape(-1, h, d)[:s]
    return mm("she,hed->sd", o * jax.nn.sigmoid(gate), p["attn/out/kernel"])


def _experts(x, p, cfg, precision):
    mm = functools.partial(reference._mm, precision=precision)
    top_k, held = cfg["num_experts_per_tok"], cfg["num_experts"]
    logits = jnp.einsum("sd,de->se", x, p["moe/router/kernel"],
                        precision="highest")
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    @jax.checkpoint
    def one(y, e):
        wi, wg, wo, expert = e
        weight = jnp.sum(jnp.where(idx == expert, gates, 0.0), axis=-1)
        hidden = jax.nn.silu(mm("sd,df->sf", x, wg)) * mm("sd,df->sf", x, wi)
        return y + weight[:, None] * mm("sf,fd->sd", hidden, wo), None

    routed, _ = lax.scan(one, jnp.zeros_like(x), (
        p["moe/experts/wi"], p["moe/experts/wg"], p["moe/experts/wo"],
        cfg["first_expert_held"] + jnp.arange(held)))
    hidden = jax.nn.silu(mm("sd,df->sf", x, p["moe/shared/wg/kernel"])) \
        * mm("sd,df->sf", x, p["moe/shared/wi/kernel"])
    shared = mm("sf,fd->sd", hidden, p["moe/shared/wo/kernel"])
    return routed + jax.nn.sigmoid(
        mm("sd,do->so", x, p["moe/shared/gate/kernel"])) * shared


def _block(x, p, cfg, precision, kind):
    eps = cfg["rms_norm_eps"]
    mixer = _gated_attention if kind == "full" else _gated_delta_net
    x = x + mixer(_norm(x, p["ln_attn/scale"], eps), p, cfg, precision)
    return x + _experts(_norm(x, p["ln_mlp/scale"], eps), p, cfg, precision)


def _row_loss_sum(params: dict, row, cfg: dict, precision: str):
    """One row of tokens [S]: its summed next-token cross entropy."""
    inputs, targets = row[:-1], row[1:]
    x = jnp.take(params["embed"], inputs, axis=0)
    for i, kind in enumerate(_layer_kinds(cfg)):
        pre = f"block_{i}/"
        block = jax.checkpoint(functools.partial(
            _block, cfg=cfg, precision=precision, kind=kind))
        x = block(x, {k[len(pre):]: v for k, v in params.items()
                      if k.startswith(pre)})
    x = _norm(x, params["ln_f/scale"], cfg["rms_norm_eps"])
    logits = reference._mm("sd,vd->sv", x, params["head"], precision)
    true = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - true)


def loss_and_grads(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """Mean loss over all targets of ``tokens`` [B, S], and its gradient:
    a row at a time, every block checkpointed."""
    def one(carry, row):
        l, g = jax.value_and_grad(_row_loss_sum)(params, row, cfg, precision)
        return (carry[0] + l, jax.tree.map(jnp.add, carry[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = lax.scan(one, zero, tokens)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    return loss / n, jax.tree.map(lambda g: g / n, grads)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone
# ---------------------------------------------------------------------------

def _forward_flops(cfg: dict, rows: int, positions: int) -> float:
    """Every matmul of the equations above over ``rows`` rows of
    ``positions`` positions: causal attention at the computed half, the
    delta rule by its recurrence (``6 * Dk * Dv`` a token a value head: the
    decay, ``S^T k``, the outer product, ``S^T q``), the routed experts by
    the rows expected here under even routing.  The conv, the norms and
    the softmaxes are not matmuls and are not counted."""
    d, t = cfg["hidden_size"], rows * positions
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    linear = (2 * t * d * (2 * hk * dk + 2 * hv * dv)     # in_qkvz
              + 2 * t * d * 2 * hv                        # in_ba
              + 6 * t * hv * dk * dv                      # the recurrence
              + 2 * t * hv * dv * d)                      # out
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    full = (2 * t * d * (2 * h * hd + 2 * kv * hd)        # q + gate, k, v
            + 2 * 2 * rows * h * positions * positions * hd * 0.5
            + 2 * t * h * hd * d)                         # out
    ff, shared = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    routed_rows = (t * cfg["num_experts_per_tok"] * cfg["num_experts"]
                   / cfg["router_num_experts"])
    experts = (2 * t * d * cfg["router_num_experts"]
               + 3 * 2 * routed_rows * d * ff
               + 3 * 2 * t * d * shared + 2 * t * d)
    kinds = _layer_kinds(cfg)
    return (kinds.count("linear") * linear + kinds.count("full") * full
            + len(kinds) * experts + 2 * t * d * cfg["vocab_size"])


def train_flops(cfg: dict, rows: int, row_tokens: int) -> float:
    """Model FLOPs of one train step: ``row_tokens - 1`` targets a row,
    backward twice the forward; recomputation and padding (the delta
    rule's chunks, the experts' buffer) never credited."""
    return 3.0 * _forward_flops(cfg, rows, row_tokens - 1)


def attention_work(cfg: dict, rows_per_chip: int, positions: int,
                   act_bytes: int = 2) -> dict:
    """The full-attention layers' work of one train step, as
    ``lib/flops.flash_train_work`` counts it, K and V (and their gradients)
    at the K/V heads the model has, not the query heads they are repeated
    to."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = _layer_kinds(cfg).count("full")
    fwd = 4 * rows_per_chip * h * positions * positions * hd * 0.5
    tensor = rows_per_chip * positions * hd * act_bytes
    return {"flops": layers * 3 * fwd,
            "bytes": layers * 6 * (h + kv) * tensor}


def delta_rule_work(cfg: dict, rows_per_chip: int, positions: int) -> dict:
    """The delta rule's least work of one train step (``gdn_roofline``):
    the recurrence's FLOPs, forward and twice that backward, and the bytes
    of q, k, v, g, beta, o and their cotangents, read or written once each
    in float32 (q and k at the key heads)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    t = rows_per_chip * positions
    layers = _layer_kinds(cfg).count("linear")
    a_token = 2 * hk * dk + 2 * hv * dv + 2 * hv     # q k, v o, g beta
    return {"flops": layers * 3 * 6 * t * hv * dk * dv,
            "bytes": layers * 2 * t * a_token * 4}


def expert_matmul_work(cfg: dict, rows_per_chip: int, positions: int,
                       act_bytes: int = 2) -> dict:
    """The grouped matmuls' least work of one train step
    (``moe_gmm_roofline``): three matmuls an expert layer over the rows
    expected here under even routing, forward and twice that backward; the
    held weights read once a pass (forward, the input's gradient) and
    their gradient written once in float32, the rows' activations in and
    out of each matmul.  Padding to the buffer's rows is not credited."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = (rows_per_chip * positions * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / cfg["router_num_experts"])
    layers = cfg["num_hidden_layers"]
    weights = 3 * cfg["num_experts"] * d * ff
    acts = rows * (2 * (d + ff) + (ff + d)) * act_bytes
    return {"flops": layers * 3 * 3 * 2 * rows * d * ff,
            "bytes": layers * (weights * (2 * act_bytes + 4) + 3 * acts)}
