"""The Kimi-Linear family (``"model_type": "kimi_linear"``): the five names
``lib/modules.py`` lists, the work functions of its own metrics, and the
plain reference behind them.  Imports nothing of the program.

**The equations** (what the reference computes; the program may reorder,
never omit).  Block ``i`` (1-based, as the configuration's
``linear_attn_config`` counts): ``h = x + Mixer_i(norm(x))``, ``y = h +
FFN_i(norm(h))``; ``Mixer_i`` is Kimi Delta Attention where ``i`` is in
``kda_layers`` and latent attention where it is in ``full_attn_layers``;
``norm`` is the plain RMSNorm ``x / sqrt(mean(x^2) + eps) * w``; ``FFN_i``
is a dense SwiGLU of ``intermediate_size`` for ``i <=
first_k_dense_replace`` and the expert layer below after that; the same
norm closes the stack, then an untied head ``[vocab, hidden]`` and the mean
next-token cross entropy.

*Kimi Delta Attention* (arXiv:2510.26692; ``num_heads`` heads of
``head_dim``, key and value alike): ``in_q``, ``in_k``, ``in_v`` project to
``heads x head_dim``, pass a causal depthwise conv (no bias) and SiLU; q and
k are L2-normalised over the head, q scaled by ``head_dim ** -0.5``; ``beta
= sigmoid(in_b x)`` a head; ``g = -exp(A_log) * softplus(f_b(f_a x) +
dt_bias)`` a key channel (``A_log`` a head, ``dt_bias`` a channel).  A
head, with ``S = 0`` at the row's start:

    S <- Diag(exp(g_t)) S;  u_t = beta_t (v_t - S^T k_t);
    S <- S + k_t u_t^T;  o_t = S^T q_t

**token by token** here (``lax.scan`` over positions, checkpointed in blocks
of :data:`_SCAN_BLOCK`), where the program computes a chunkwise form.  Then
``out(RMSNorm(o_t) * w * sigmoid(g_b(g_a x) + b_g))``.

*Latent attention, no rotation* (MLA, NoPE): ``q = q x`` a head of
``qk_nope_head_dim + qk_rope_head_dim``; ``c = kv_a x``, of which the first
``kv_lora_rank`` pass an RMSNorm and ``kv_b`` to each head's
``qk_nope_head_dim`` of key and ``v_head_dim`` of value, and the last
``qk_rope_head_dim`` are one key part for all heads, **not rotated**; causal
softmax attention scaled by the query/key size, in blocks of
:data:`_QUERY_BLOCK` queries; ``out``.

*Experts*: ``s = sigmoid(router x)`` over all ``router_num_experts`` in
float32, the ``num_experts_per_token`` largest (one group: the grouped
top-k is the plain one), divided by their sum, times
``routed_scaling_factor``; the routed output is the sum over the chosen
experts **that are held here** (``first_expert_held ... + num_experts``), a
plain loop over the held experts with masks; plus the shared expert's
SwiGLU, no gate.  No balance term, no selection bias.

``precision`` as in ``lib/reference.py``: every matmul's operands rounded
(``"bf16"``, ``"fp8"`` the control), the rule's q, k, v too.
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
    linear = cfg["linear_attn_config"]
    kinds = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if (i in linear["kda_layers"]) == (i in linear["full_attn_layers"]):
            raise ValueError(f"layer {i} is not of exactly one kind")
        kinds.append("kda" if i in linear["kda_layers"] else "mla")
    return tuple(kinds)


def model_kwargs(cfg: dict, remat: bool) -> dict:
    """The program's names for the configuration file's (HF) keys."""
    if (cfg["moe_layer_freq"] != 1 or cfg["tie_word_embeddings"]
            or not cfg["moe_renormalize"] or not cfg["mla_use_nope"]
            or cfg["q_lora_rank"] is not None or cfg["num_expert_group"] != 1
            or cfg["topk_group"] != 1 or cfg["num_shared_experts"] != 1):
        raise ValueError(
            "the kimi_linear family routes every layer behind the leading "
            "dense ones in one group, renormalises the chosen scores, has "
            "one shared expert, an untied head, and latent attention "
            "without a rotation or a query latent")
    linear = cfg["linear_attn_config"]
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["model_max_length"], attn_impl="flash", remat=remat,
        layer_kinds=_layer_kinds(cfg), norm_eps=cfg["rms_norm_eps"],
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_gate_rank=cfg["kda_gate_rank"],
        kda_conv=linear["short_conv_kernel_size"],
        mla_nope_dim=cfg["qk_nope_head_dim"],
        mla_rope_dim=cfg["qk_rope_head_dim"], mla_v_dim=cfg["v_head_dim"],
        mla_kv_rank=cfg["kv_lora_rank"],
        n_experts=cfg["num_experts"], moe_every=1, moe_dispatch="held",
        first_dense_layers=cfg["first_k_dense_replace"],
        moe_router_width=cfg["router_num_experts"],
        moe_first_expert=cfg["first_expert_held"],
        moe_top_k=cfg["num_experts_per_token"],
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_shared_d_ff=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        moe_router_act=cfg["moe_router_activation_func"],
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_shared_gate=False, tie_embeddings=False)


def leaf_moments(path: str, shape) -> tuple[float, float]:
    """``(mean, std)`` of a leaf's normal draw (each listed under the
    configuration's ``assumed``): kernels ``1 / sqrt(fan_in)``; norm weights
    around 1; conv taps so that the conv's output has its input's scale; the
    gate's bias around 0; ``A_log`` a head and ``dt_bias`` a channel so that
    the median decay ``exp(g)`` a channel is about 0.99 a token and nearly
    all of them lie between 0.9 and 0.999."""
    name = path.split("/")
    leaf = name[-1]
    if leaf == "scale":
        return 1.0, 0.1
    if leaf in ("embed", "head"):
        return 0.0, 0.02
    if leaf == "A_log":
        # g = -exp(A_log) softplus(z + dt_bias), z ~ N(0, 1): the median
        # softplus is 1.31, so exp(-4.7) * 1.31 = 0.0119 and exp(g) = 0.988;
        # two deviations of both: 0.0007 to 0.11, exp(g) 0.894 to 0.9993
        return -4.7, 0.7
    if leaf == "dt_bias":
        return 1.0, 0.3
    if leaf == "bias":
        return 0.0, 0.1
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
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(x, wi, wg, wo, mm):
    return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, wg))
              * mm("sd,df->sf", x, wi), wo)


def _delta_rule(q, k, v, g, beta):
    """The recurrence, token by token.  q, k, g [S, H, Dk], v [S, H, Dv],
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
        state = jnp.exp(g_t)[:, :, None] * state
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


def _kda(x, p, cfg, precision):
    mm = functools.partial(reference._mm, precision=precision)
    d = cfg["linear_attn_config"]["head_dim"]
    s = x.shape[0]
    q, k, v = (mm("sd,dhe->she", x, p[f"kda/in_{n}/kernel"]) for n in "qkv")
    h = q.shape[1]
    mixed = jnp.concatenate([t.reshape(s, -1) for t in (q, k, v)], axis=-1)
    taps = p["kda/conv/kernel"]
    width = taps.shape[0]
    padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(taps[j] * padded[j:j + s] for j in range(width)))
    q, k, v = (t.reshape(s, h, d) for t in jnp.split(mixed, 3, axis=-1))

    def l2(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                             + cfg["kda_l2norm_eps"])

    q, k = l2(q) * d ** -0.5, l2(k)
    beta = jax.nn.sigmoid(mm("sd,dh->sh", x, p["kda/in_b/kernel"]))
    low = mm("sd,dr->sr", x, p["kda/f_a/kernel"])
    g = -jnp.exp(p["kda/A_log"])[:, None] * jax.nn.softplus(
        mm("sr,rhe->she", low, p["kda/f_b/kernel"]) + p["kda/dt_bias"])
    o = _delta_rule(*(_rounded(t, precision) for t in (q, k, v)), g, beta)
    o = _norm(o, p["kda/norm/scale"], cfg["rms_norm_eps"])
    gate = mm("sr,rhe->she", mm("sd,dr->sr", x, p["kda/g_a/kernel"]),
              p["kda/g_b/kernel"]) + p["kda/g_b/bias"]
    return mm("she,hed->sd", o * jax.nn.sigmoid(gate), p["kda/out/kernel"])


def _mla(x, p, cfg, precision):
    mm = functools.partial(reference._mm, precision=precision)
    nope, shared = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, s = cfg["kv_lora_rank"], x.shape[0]
    q = mm("sd,dhe->she", x, p["attn/q/kernel"])         # [S, H, 192]
    h = q.shape[1]
    latent = mm("sd,dr->sr", x, p["attn/kv_a/kernel"])
    kv = mm("sr,rhe->she",
            _norm(latent[:, :rank], p["attn/kv_norm/scale"],
                  cfg["rms_norm_eps"]), p["attn/kv_b/kernel"])
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(latent[:, None, rank:], (s, h, shared))], axis=-1)
    v = kv[..., nope:]
    width = nope + shared
    pad = -s % _QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, _QUERY_BLOCK, h, width)
    first = jnp.arange(qb.shape[0]) * _QUERY_BLOCK

    @jax.checkpoint
    def queries(args):
        q_blk, start = args                              # [Q, H, 192]
        scores = mm("qhe,khe->hqk", q_blk, k) / jnp.sqrt(jnp.float32(width))
        seen = (start + jnp.arange(_QUERY_BLOCK))[:, None] \
            >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("hqk,khe->qhe", probs, v)

    o = lax.map(queries, (qb, first)).reshape(-1, h, v.shape[-1])[:s]
    return mm("she,hed->sd", o, p["attn/out/kernel"])


def _router(x, p, cfg):
    """``(gates, idx)`` [S, k]: the sigmoid scores of the chosen experts,
    divided by their sum, times the scaling factor, and their ids."""
    logits = jnp.einsum("sd,de->se", x, p["moe/router/kernel"],
                        precision="highest")
    gates, idx = lax.top_k(jax.nn.sigmoid(logits),
                           cfg["num_experts_per_token"])
    return (gates / jnp.sum(gates, axis=-1, keepdims=True)
            * cfg["routed_scaling_factor"], idx)


def _routed(x, p, cfg, precision, first, held_weights):
    """The routed output over the experts ``first ...`` whose weights are
    ``held_weights`` (``wi, wg, wo`` stacked): a masked loop."""
    mm = functools.partial(reference._mm, precision=precision)
    gates, idx = _router(x, p, cfg)

    @jax.checkpoint
    def one(y, e):
        wi, wg, wo, expert = e
        weight = jnp.sum(jnp.where(idx == expert, gates, 0.0), axis=-1)
        return y + weight[:, None] * _swiglu(x, wi, wg, wo, mm), None

    wi = held_weights[0]
    routed, _ = lax.scan(one, jnp.zeros_like(x), (
        *held_weights, first + jnp.arange(wi.shape[0])))
    return routed


def _experts(x, p, cfg, precision):
    mm = functools.partial(reference._mm, precision=precision)
    routed = _routed(x, p, cfg, precision, cfg["first_expert_held"],
                     (p["moe/experts/wi"], p["moe/experts/wg"],
                      p["moe/experts/wo"]))
    return routed + _swiglu(x, p["moe/shared/wi/kernel"],
                            p["moe/shared/wg/kernel"],
                            p["moe/shared/wo/kernel"], mm)


def _block(x, p, cfg, precision, kind, dense):
    eps = cfg["rms_norm_eps"]
    mixer = _kda if kind == "kda" else _mla
    x = x + mixer(_norm(x, p["ln_attn/scale"], eps), p, cfg, precision)
    h = _norm(x, p["ln_mlp/scale"], eps)
    if dense:
        mm = functools.partial(reference._mm, precision=precision)
        return x + _swiglu(h, p["mlp/wi/kernel"], p["mlp/wg/kernel"],
                           p["mlp/wo/kernel"], mm)
    return x + _experts(h, p, cfg, precision)


def _row_loss_sum(params: dict, row, cfg: dict, precision: str):
    """One row of tokens [S]: its summed next-token cross entropy."""
    inputs, targets = row[:-1], row[1:]
    x = jnp.take(params["embed"], inputs, axis=0)
    for i, kind in enumerate(_layer_kinds(cfg)):
        pre = f"block_{i}/"
        block = jax.checkpoint(functools.partial(
            _block, cfg=cfg, precision=precision, kind=kind,
            dense=i < cfg["first_k_dense_replace"]))
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

def _sizes(cfg: dict):
    linear = cfg["linear_attn_config"]
    return (cfg["hidden_size"], linear["num_heads"], linear["head_dim"],
            cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _forward_flops(cfg: dict, rows: int, positions: int) -> float:
    """Every matmul of the equations above over ``rows`` rows of
    ``positions`` positions: causal attention at the computed half (scores
    over the query/key size, the weighted sum over the value's), the delta
    rule by its recurrence (``6 * Dk * Dv`` a token a head), the routed
    experts by the rows expected here under even routing.  The conv, the
    norms, the softmax and the sigmoids are not matmuls and are not
    counted."""
    d, hk, dk, h, qk, dv = _sizes(cfg)
    t = rows * positions
    rank = cfg["kda_gate_rank"]
    kda = (2 * t * d * 4 * hk * dk                        # in_q k v, out
           + 2 * t * d * hk                               # in_b
           + 2 * 2 * t * rank * (d + hk * dk)             # f_a f_b, g_a g_b
           + 6 * t * hk * dk * dk)                        # the recurrence
    latent = cfg["kv_lora_rank"]
    nope = cfg["qk_nope_head_dim"]
    mla = (2 * t * d * h * qk                             # q
           + 2 * t * d * (latent + cfg["qk_rope_head_dim"])
           + 2 * t * latent * h * (nope + dv)             # kv_b
           + 2 * rows * h * positions * positions * (qk + dv) * 0.5
           + 2 * t * h * dv * d)                          # out
    ff = cfg["moe_intermediate_size"]
    routed_rows = (t * cfg["num_experts_per_token"] * cfg["num_experts"]
                   / cfg["router_num_experts"])
    experts = (2 * t * d * cfg["router_num_experts"]
               + 3 * 2 * routed_rows * d * ff
               + 3 * 2 * t * d * ff * cfg["num_shared_experts"])
    dense = 3 * 2 * t * d * cfg["intermediate_size"]
    kinds = _layer_kinds(cfg)
    n_dense = cfg["first_k_dense_replace"]
    return (kinds.count("kda") * kda + kinds.count("mla") * mla
            + n_dense * dense + (len(kinds) - n_dense) * experts
            + 2 * t * d * cfg["vocab_size"])


def train_flops(cfg: dict, rows: int, row_tokens: int) -> float:
    """Model FLOPs of one train step: ``row_tokens - 1`` targets a row,
    backward twice the forward; recomputation and padding (the rule's
    chunks, the experts' buffer) never credited."""
    return 3.0 * _forward_flops(cfg, rows, row_tokens - 1)


def attention_work(cfg: dict, rows_per_chip: int, positions: int,
                   act_bytes: int = 2) -> dict:
    """The latent-attention layers' work of one train step, as
    ``lib/flops.flash_train_work`` counts it at two head sizes: ``Q K^T``
    over the query/key size (192, not the 256 a padded kernel would
    multiply), ``P V`` over the value's; q, k, dq, dk at the one, v, o, do,
    dv at the other."""
    _, _, _, h, qk, dv = _sizes(cfg)
    layers = _layer_kinds(cfg).count("mla")
    fwd = 2 * rows_per_chip * h * positions * positions * (qk + dv) * 0.5
    token = rows_per_chip * h * positions * act_bytes
    return {"flops": layers * 3 * fwd,
            "bytes": layers * token * (6 * qk + 6 * dv)}


def kda_rule_work(cfg: dict, rows_per_chip: int, positions: int) -> dict:
    """The channel-wise rule's least work of one train step
    (``kda_roofline``): the recurrence's FLOPs (``6 * Dk * Dv`` a token a
    head), forward and twice that backward, and the bytes of q, k, v, **g
    at its key channels**, beta, o and their cotangents, read or written
    once each in float32."""
    _, hk, dk, _, _, _ = _sizes(cfg)
    t = rows_per_chip * positions
    layers = _layer_kinds(cfg).count("kda")
    a_token = hk * (5 * dk + 1)                     # q k v g o, beta
    return {"flops": layers * 3 * 6 * t * hk * dk * dk,
            "bytes": layers * 2 * t * a_token * 4}


def expert_matmul_work(cfg: dict, rows_per_chip: int, positions: int,
                       act_bytes: int = 2) -> dict:
    """The grouped matmuls' least work of one train step, as the
    ``qwen3_next`` family counts it: three matmuls an expert layer over the
    rows expected here under even routing, forward and twice that backward;
    the held weights read once a pass and their gradient written once in
    float32, the rows' activations in and out of each matmul."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = (rows_per_chip * positions * cfg["num_experts_per_token"]
            * cfg["num_experts"] / cfg["router_num_experts"])
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    weights = 3 * cfg["num_experts"] * d * ff
    acts = rows * (2 * (d + ff) + (ff + d)) * act_bytes
    return {"flops": layers * 3 * 3 * 2 * rows * d * ff,
            "bytes": layers * (weights * (2 * act_bytes + 4) + 3 * acts)}
