"""The JoyAI-LLM-Flash family (``"model_type": "joyai_llm_flash"``): the five
names ``lib/modules.py`` lists, the work functions of its own metrics, and
the plain reference behind them.  Imports nothing of the program.

**The equations** (what the reference computes; the program may reorder,
never omit).  ``norm`` is the plain RMSNorm ``x / sqrt(mean(x^2) + eps) *
w``.  Block ``i`` (0-based): ``h = x + MLA(norm(x))``, ``y = h +
FFN(norm(h))``; ``FFN`` is a dense SwiGLU of ``intermediate_size`` for ``i
< first_k_dense_replace`` and the expert layer below after that.  The same
norm closes the stack, then an untied head ``[vocab, hidden]`` and the mean
next-token cross entropy.

*Latent attention* on every layer (MLA with a query latent and a rotated
shared key part): ``c_q = norm(q_a x)`` of ``q_lora_rank``; ``q = q_b c_q``,
a head of ``qk_nope_head_dim | qk_rope_head_dim``; ``[c_kv | k_rope] = kv_a
x`` of ``kv_lora_rank | qk_rope_head_dim``; ``[k_nope | v] = kv_b
norm(c_kv)``, a head of ``qk_nope_head_dim | v_head_dim``.  The query's rope
part and ``k_rope`` (one part for all heads) are rotated by position ``p``:
with ``rope_interleave`` the adjacent pair ``(2i, 2i + 1)`` turns by ``p *
rope_theta ** (-2i / qk_rope_head_dim)`` (:func:`_rotated`, by strided
slices).  Causal softmax attention scaled by the query/key size, in blocks
of :data:`_QUERY_BLOCK` queries; ``o_proj``.

*Experts*: ``s = sigmoid(router x)`` over all ``router_num_experts`` in
float32; the ``num_experts_per_tok`` largest by ``s`` (``noaux_tc`` with its
selection bias held at zero, one group: the grouped top-k is the plain one),
divided by their sum, times ``routed_scaling_factor``; the routed output is
the sum over the chosen experts **that are held here**
(``first_expert_held ... + n_routed_experts``), a plain loop over the held
experts with masks; plus the shared expert's SwiGLU, no gate.  No balance
term.

*Multi-token prediction* (``num_nextn_predict_layers`` 1; DeepSeek-V3's
module, arXiv:2412.19437): at position ``i`` of the row's inputs ``t_0 ...
t_{S-2}``, ``x_i = eh_proj [enorm(Emb t_{i+1}); hnorm(h_i)]`` with ``h`` the
last block's output before the final norm, then one expert block of its own
(its own latent attention, router and held experts) over positions ``0 ...
S - 3``, then ``head_norm`` and the model's head: position ``i`` predicts
``t_{i+2}``.  The loss is the next-token mean plus ``mtp_loss_weight`` times
the module's mean, each over its own targets.

``precision`` as in ``lib/reference.py``: every matmul's operands rounded
(``"bf16"``, ``"fp8"`` the control).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from lib import reference

_QUERY_BLOCK = 512      # queries whose f32 scores are held at once


# ---------------------------------------------------------------------------
# the program's keywords, and how its leaves are drawn
# ---------------------------------------------------------------------------

def model_kwargs(cfg: dict, remat: bool) -> dict:
    """The program's names for the configuration file's (HF) keys."""
    if (cfg["moe_layer_freq"] != 1 or cfg["tie_word_embeddings"]
            or not cfg["norm_topk_prob"] or cfg["scoring_func"] != "sigmoid"
            or cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or cfg["n_shared_experts"] != 1
            or cfg["rope_scaling"] is not None or cfg["attention_bias"]
            or cfg["hidden_act"] != "silu" or not cfg["rope_interleave"]):
        raise ValueError(
            "the joyai_llm_flash family routes every layer behind the "
            "leading dense ones by sigmoid scores in one group, renormalises "
            "them, has one shared expert, an untied head, SwiGLU, no "
            "attention bias and no rope scaling")
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], attn_impl="flash",
        remat=remat, layer_kinds=("mla",) * cfg["num_hidden_layers"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        mla_nope_dim=cfg["qk_nope_head_dim"],
        mla_rope_dim=cfg["qk_rope_head_dim"], mla_v_dim=cfg["v_head_dim"],
        mla_kv_rank=cfg["kv_lora_rank"], mla_q_rank=cfg["q_lora_rank"],
        mla_rope="pairs",
        n_experts=cfg["n_routed_experts"], moe_every=1, moe_dispatch="held",
        first_dense_layers=cfg["first_k_dense_replace"],
        moe_router_width=cfg["router_num_experts"],
        moe_first_expert=cfg["first_expert_held"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_shared_d_ff=cfg["moe_intermediate_size"]
        * cfg["n_shared_experts"],
        moe_router_act="sigmoid",
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_shared_gate=False, tie_embeddings=False,
        mtp_layers=cfg["num_nextn_predict_layers"],
        mtp_loss_weight=float(cfg["mtp_loss_weight"]))


def leaf_moments(path: str, shape) -> tuple[float, float]:
    """``(mean, std)`` of a leaf's normal draw (listed under the
    configuration's ``assumed``): kernels ``1 / sqrt(fan_in)``, stacked
    expert tensors by their own contraction axis; norm weights around 1;
    the embedding and the head ``0.02``."""
    name = path.split("/")
    leaf = name[-1]
    if leaf == "scale":
        return 1.0, 0.1
    if leaf in ("embed", "head"):
        return 0.0, 0.02
    if leaf in ("wi", "wg", "wo") and name[-2] == "experts":
        return 0.0, 1.0 / math.sqrt(shape[1])       # [held, in, out]
    if leaf == "kernel":
        # the output projection contracts (heads, head size); all others
        # contract their first axis
        fan_in = shape[0] * shape[1] if name[-2] == "out" else shape[0]
        return 0.0, 1.0 / math.sqrt(fan_in)
    raise ValueError(f"no rule to generate parameter leaf {path!r}")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(x, wi, wg, wo, mm):
    return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, wg))
              * mm("sd,df->sf", x, wi), wo)


def _rotated(x, cfg):
    """``x`` [S, ..., r] turned by position: frequency ``i`` turns the
    adjacent pair ``(2i, 2i + 1)`` (``rope_interleave``, which the family
    requires) by ``p * rope_theta ** (-2i / r)``."""
    s, r = x.shape[0], x.shape[-1]
    inv = float(cfg["rope_theta"]) ** (
        -jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (s,) + (1,) * (x.ndim - 2) + (r // 2,)
    c, sn = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.zeros_like(x).at[..., 0::2].set(even * c - odd * sn) \
        .at[..., 1::2].set(even * sn + odd * c)


def _mla(x, p, cfg, precision):
    mm = functools.partial(reference._mm, precision=precision)
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, s, eps = cfg["kv_lora_rank"], x.shape[0], cfg["rms_norm_eps"]
    c_q = _norm(mm("sd,dr->sr", x, p["attn/q_a/kernel"]),
                p["attn/q_a_norm/scale"], eps)
    q = mm("sr,rhe->she", c_q, p["attn/q_b/kernel"])     # [S, H, 192]
    h = q.shape[1]
    q = jnp.concatenate([q[..., :nope], _rotated(q[..., nope:], cfg)],
                        axis=-1)
    latent = mm("sd,dr->sr", x, p["attn/kv_a/kernel"])
    kv = mm("sr,rhe->she", _norm(latent[:, :rank], p["attn/kv_norm/scale"],
                                 eps), p["attn/kv_b/kernel"])
    shared = _rotated(latent[:, rank:], cfg)             # [S, 64]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(shared[:, None], (s, h, rope))],
        axis=-1)
    v = kv[..., nope:]
    width = nope + rope
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
                           cfg["num_experts_per_tok"])
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

    routed, _ = lax.scan(one, jnp.zeros_like(x), (
        *held_weights, first + jnp.arange(held_weights[0].shape[0])))
    return routed


def _experts(x, p, cfg, precision):
    mm = functools.partial(reference._mm, precision=precision)
    routed = _routed(x, p, cfg, precision, cfg["first_expert_held"],
                     (p["moe/experts/wi"], p["moe/experts/wg"],
                      p["moe/experts/wo"]))
    return routed + _swiglu(x, p["moe/shared/wi/kernel"],
                            p["moe/shared/wg/kernel"],
                            p["moe/shared/wo/kernel"], mm)


def _block(x, p, cfg, precision, dense):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(_norm(x, p["ln_attn/scale"], eps), p, cfg, precision)
    h = _norm(x, p["ln_mlp/scale"], eps)
    if dense:
        mm = functools.partial(reference._mm, precision=precision)
        return x + _swiglu(h, p["mlp/wi/kernel"], p["mlp/wg/kernel"],
                           p["mlp/wo/kernel"], mm)
    return x + _experts(h, p, cfg, precision)


def _under(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _cross_entropy_sum(logits, targets):
    true = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - true)


def _row_loss_sums(params: dict, row, cfg: dict, precision: str):
    """One row of tokens [S]: the summed next-token cross entropy and the
    multi-token-prediction module's summed cross entropy over the targets
    two ahead (0 where the configuration has no module)."""
    eps = cfg["rms_norm_eps"]
    inputs, targets = row[:-1], row[1:]
    x = jnp.take(params["embed"], inputs, axis=0)
    dense, layers = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    for i in range(min(dense, layers)):
        x = jax.checkpoint(functools.partial(
            _block, cfg=cfg, precision=precision, dense=True))(
            x, _under(params, f"block_{i}/"))
    if layers > dense:
        # the expert layers in one scan over their leaves stacked by layer:
        # one block of code for all of them, not one a layer
        experts = jax.checkpoint(functools.partial(
            _block, cfg=cfg, precision=precision, dense=False))
        stacked = jax.tree.map(lambda *leaf: jnp.stack(leaf), *[
            _under(params, f"block_{i}/") for i in range(dense, layers)])
        x, _ = lax.scan(lambda x, p: (experts(x, p), None), x, stacked)
    head = functools.partial(reference._mm, "sd,vd->sv", b=params["head"],
                             precision=precision)
    main = _cross_entropy_sum(
        head(_norm(x, params["ln_f/scale"], eps)), targets)
    if not cfg["num_nextn_predict_layers"]:
        return main, jnp.float32(0)
    p = _under(params, "mtp/")
    nxt = _norm(jnp.take(params["embed"], inputs[1:], axis=0),
                p["enorm/scale"], eps)
    y = reference._mm("sc,cd->sd", jnp.concatenate(
        [nxt, _norm(x[:-1], p["hnorm/scale"], eps)], axis=-1),
        p["eh_proj/kernel"], precision)
    y = jax.checkpoint(functools.partial(
        _block, cfg=cfg, precision=precision, dense=False))(
        y, _under(p, "block/"))
    ahead = _cross_entropy_sum(head(_norm(y, p["head_norm/scale"], eps)),
                               targets[1:])
    return main, ahead


def loss_and_grads(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """The loss over all targets of ``tokens`` [B, S] (the next-token mean
    plus ``mtp_loss_weight`` times the module's mean), and its gradient: a
    row at a time, every block checkpointed, the expert layers by a scan."""
    rows, s = tokens.shape
    weight = float(cfg["mtp_loss_weight"]) \
        if cfg["num_nextn_predict_layers"] else 0.0
    n_main, n_ahead = rows * (s - 1), rows * (s - 2)

    def row_loss(params, row):
        main, ahead = _row_loss_sums(params, row, cfg, precision)
        return main / n_main + weight * ahead / n_ahead

    def one(carry, row):
        l, g = jax.value_and_grad(row_loss)(params, row)
        return (carry[0] + l, jax.tree.map(jnp.add, carry[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = lax.scan(one, zero, tokens)
    return loss, grads


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone
# ---------------------------------------------------------------------------

def _mla_flops(cfg: dict, rows: int, positions: int) -> float:
    """One latent-attention layer's matmuls over ``rows`` rows of
    ``positions``: the two steps to q, the two to keys and values, causal
    attention at the computed half (scores over the query/key size, the
    weighted sum over the value's), the output projection."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qk, t = nope + rope, rows * positions
    return (2 * t * d * cfg["q_lora_rank"]
            + 2 * t * cfg["q_lora_rank"] * h * qk
            + 2 * t * d * (cfg["kv_lora_rank"] + rope)
            + 2 * t * cfg["kv_lora_rank"] * h * (nope + dv)
            + 2 * rows * h * positions * positions * (qk + dv) * 0.5
            + 2 * t * h * dv * d)


def _expert_flops(cfg: dict, tokens: int) -> float:
    """An expert layer: the router over all experts, the routed experts by
    the rows expected here under even routing, the shared expert."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed_rows = (tokens * cfg["num_experts_per_tok"]
                   * cfg["n_routed_experts"] / cfg["router_num_experts"])
    return (2 * tokens * d * cfg["router_num_experts"]
            + 3 * 2 * routed_rows * d * ff
            + 3 * 2 * tokens * d * ff * cfg["n_shared_experts"])


def _forward_flops(cfg: dict, rows: int, positions: int) -> float:
    """Every matmul of the equations above over ``rows`` rows of
    ``positions`` inputs, the module's ``positions - 1`` with them.  The
    norms, the rotation, the softmax and the sigmoids are not matmuls and
    are not counted."""
    d, t = cfg["hidden_size"], rows * positions
    n_dense = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"]
    flops = (n * _mla_flops(cfg, rows, positions)
             + n_dense * 3 * 2 * t * d * cfg["intermediate_size"]
             + (n - n_dense) * _expert_flops(cfg, t)
             + 2 * t * d * cfg["vocab_size"])
    if cfg["num_nextn_predict_layers"]:
        ahead = rows * (positions - 1)
        flops += (2 * ahead * 2 * d * d                    # eh_proj
                  + _mla_flops(cfg, rows, positions - 1)
                  + _expert_flops(cfg, ahead)
                  + 2 * ahead * d * cfg["vocab_size"])     # its head
    return flops


def train_flops(cfg: dict, rows: int, row_tokens: int) -> float:
    """Model FLOPs of one train step: ``row_tokens - 1`` inputs a row,
    backward twice the forward; recomputation and padding (the experts'
    buffer) never credited."""
    return 3.0 * _forward_flops(cfg, rows, row_tokens - 1)


def _latent_layers(cfg: dict, positions: int) -> list:
    """The positions of each latent-attention layer of a step: every block
    at ``positions``, the module's one less."""
    return ([positions] * cfg["num_hidden_layers"]
            + [positions - 1] * cfg["num_nextn_predict_layers"])


def attention_work(cfg: dict, rows_per_chip: int, positions: int,
                   act_bytes: int = 2) -> dict:
    """The latent-attention layers' work of one train step, as
    ``lib/flops.flash_train_work`` counts it at two head sizes: ``Q K^T``
    over the query/key size (192, not the 256 a padded kernel would
    multiply), ``P V`` over the value's; q, k, dq, dk at the one, v, o, do,
    dv at the other; the module's layer at its own positions."""
    h, dv = cfg["num_attention_heads"], cfg["v_head_dim"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    flops = bytes_ = 0.0
    for p in _latent_layers(cfg, positions):
        flops += 3 * 2 * rows_per_chip * h * p * p * (qk + dv) * 0.5
        bytes_ += rows_per_chip * h * p * act_bytes * (6 * qk + 6 * dv)
    return {"flops": flops, "bytes": bytes_}


def expert_matmul_work(cfg: dict, rows_per_chip: int, positions: int,
                       act_bytes: int = 2) -> dict:
    """The grouped matmuls' least work of one train step, as the
    ``kimi_linear`` family counts it: three matmuls an expert layer over the
    rows expected here under even routing, forward and twice that backward;
    the held weights read once a pass and their gradient written once in
    float32, the rows' activations in and out of each matmul.  The module's
    expert layer at its own positions."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 3 * cfg["n_routed_experts"] * d * ff
    layers = ([positions] * (cfg["num_hidden_layers"]
                             - cfg["first_k_dense_replace"])
              + [positions - 1] * cfg["num_nextn_predict_layers"])
    flops = bytes_ = 0.0
    for p in layers:
        rows = (rows_per_chip * p * cfg["num_experts_per_tok"]
                * cfg["n_routed_experts"] / cfg["router_num_experts"])
        acts = rows * (2 * (d + ff) + (ff + d)) * act_bytes
        flops += 3 * 3 * 2 * rows * d * ff
        bytes_ += weights * (2 * act_bytes + 4) + 3 * acts
    return {"flops": flops, "bytes": bytes_}
