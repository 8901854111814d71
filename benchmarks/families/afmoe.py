"""The AFMoE family (``"model_type": "afmoe"``, Arcee's Trinity models): the
five names ``lib/modules.py`` lists, the work functions of its own metrics,
and the plain reference behind them.  Imports nothing of the program.

**The equations** (what the reference computes; the program may reorder,
never omit), from the configuration's keys and the public ``afmoe``
modelling code that ``transformers`` carries for it.

*Model.*  ``x0 = sqrt(hidden_size) * E[tokens]`` (``mup_enabled``); the
blocks; ``RMSNorm``; an untied head ``[vocab, hidden]``; the mean next-token
cross entropy.

*Block* (four norms, all the plain ``RMSNorm(x) = x / sqrt(mean(x^2) + eps)
* w``): ``h = x + N2(Attn(N1(x)))``, ``y = h + N4(FFN(N3(h)))``.

*Attention* (``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads of ``head_dim``, no bias): q, k, v
projections; q and k pass an RMSNorm over the head; **on a
``sliding_attention`` layer** q and k are rotated over the whole head
(``rope_theta``, half-split pairing) and query ``i`` sees keys ``j`` with
``i - sliding_window < j <= i`` (``sliding_window`` keys with its own); **on
a ``full_attention`` layer** there is **no rotation** and the mask is
causal; softmax scaled by ``head_dim ** -0.5``, in blocks of
:data:`_QUERY_BLOCK` queries with the band as an explicit mask; the heads'
output is multiplied elementwise by ``sigmoid(G x)``, ``G`` a projection of
its own ``[hidden, heads * head_dim]``; then ``out``.

*FFN.*  A dense SwiGLU of ``intermediate_size`` in the ``num_dense_layers``
leading layers; after them ``s = sigmoid(R x)`` in float32 over all
``router_num_experts`` experts, the ``num_experts_per_tok`` largest (one
group), divided by their sum (``route_norm``), times ``route_scale``, over
SwiGLU experts of ``moe_intermediate_size``: the sum over the chosen experts
**that are held here** (``first_expert_held ... + num_experts``), a plain
loop over the held experts with masks; plus one shared SwiGLU expert, no
gate.  No balance term, no selection bias.

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
_WINDOWED = "sliding_attention"
_FULL = "full_attention"


# ---------------------------------------------------------------------------
# the program's keywords, and how its leaves are drawn
# ---------------------------------------------------------------------------

def _layer_types(cfg: dict) -> tuple:
    """The kinds of the ``num_hidden_layers`` layers: the list's first ones
    (``tools/topology_compile.py --layers`` tries smaller depths)."""
    kinds = tuple(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    if len(kinds) != cfg["num_hidden_layers"] \
            or set(kinds) - {_WINDOWED, _FULL}:
        raise ValueError(f"{len(kinds)} layer types {sorted(set(kinds))} "
                         f"for {cfg['num_hidden_layers']} layers")
    return kinds


def model_kwargs(cfg: dict, remat: bool) -> dict:
    """The program's names for the configuration file's (HF) keys."""
    if (cfg["tie_word_embeddings"] or not cfg["route_norm"]
            or cfg["score_func"] != "sigmoid" or not cfg["mup_enabled"]
            or cfg["num_expert_groups"] != 1 or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or cfg["num_limited_groups"] != 1
            or cfg["num_shared_experts"] != 1
            or cfg["rope_scaling"] is not None):
        raise ValueError(
            "the afmoe family scales the embedding, routes by a sigmoid in "
            "one group and renormalises the chosen scores, has one shared "
            "expert, an untied head and a plain rotation")
    kinds = _layer_types(cfg)
    windowed = [kind == _WINDOWED for kind in kinds]
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        attn_head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], attn_impl="flash",
        remat=remat, norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), qk_norm=True, attn_gate="own",
        layer_windows=tuple(cfg["sliding_window"] if w else 0
                            for w in windowed),
        layer_rotates=tuple(windowed), post_norms=True,
        embed_scale=math.sqrt(cfg["hidden_size"]),
        n_experts=cfg["num_experts"], moe_every=1, moe_dispatch="held",
        first_dense_layers=cfg["num_dense_layers"],
        moe_router_width=cfg["router_num_experts"],
        moe_first_expert=cfg["first_expert_held"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_shared_d_ff=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        moe_router_act=cfg["score_func"],
        moe_routed_scale=float(cfg["route_scale"]),
        moe_shared_gate=False, tie_embeddings=False)


def leaf_moments(path: str, shape) -> tuple[float, float]:
    """``(mean, std)`` of a leaf's normal draw (listed under the
    configuration's ``assumed``): kernels ``1 / sqrt(fan_in)``, norm weights
    around 1, embedding and head 0.02."""
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


def band_mask(queries, keys, window):
    """[Q, K] bool: key ``j`` is seen by query ``i`` (position ids);
    ``window`` None for the causal mask."""
    seen = queries[:, None] >= keys[None, :]
    if window is not None:
        seen &= queries[:, None] - window < keys[None, :]
    return seen


def _attention(x, p, cfg, precision, windowed):
    mm = functools.partial(reference._mm, precision=precision)
    eps, d, s = cfg["rms_norm_eps"], cfg["head_dim"], x.shape[0]
    q = _norm(mm("sd,dhe->she", x, p["attn/q/kernel"]),
              p["attn/q_norm/scale"], eps)               # [S, H, D]
    k = _norm(mm("sd,dhe->she", x, p["attn/k/kernel"]),
              p["attn/k_norm/scale"], eps)               # [S, KV, D]
    v = mm("sd,dhe->she", x, p["attn/v/kernel"])
    if windowed:
        q, k = (reference._rope(t, cfg["rope_theta"]) for t in (q, k))
    h, kv = q.shape[1], k.shape[1]
    pad = -s % _QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, _QUERY_BLOCK, kv, h // kv, d)
    first = jnp.arange(qb.shape[0]) * _QUERY_BLOCK
    window = cfg["sliding_window"] if windowed else None

    @jax.checkpoint
    def queries(args):
        q_blk, start = args                              # [Q, KV, G, D]
        scores = mm("qgre,kge->grqk", q_blk, k) * d ** -0.5
        # a padded query (past the row's end) stands on the last position
        seen = band_mask(
            jnp.minimum(start + jnp.arange(_QUERY_BLOCK), s - 1),
            jnp.arange(s), window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("grqk,kge->qgre", probs, v)

    o = lax.map(queries, (qb, first)).reshape(-1, h, d)[:s]
    gate = jax.nn.sigmoid(mm("sd,dhe->she", x, p["attn/gate_proj/kernel"]))
    return mm("she,hed->sd", o * gate, p["attn/out/kernel"])


def _router(x, p, cfg):
    """``(gates, idx)`` [S, k]: the sigmoid scores of the chosen experts,
    divided by their sum, times ``route_scale``, and their ids."""
    logits = jnp.einsum("sd,de->se", x, p["moe/router/kernel"],
                        precision="highest")
    gates, idx = lax.top_k(jax.nn.sigmoid(logits),
                           cfg["num_experts_per_tok"])
    return (gates / jnp.sum(gates, axis=-1, keepdims=True)
            * cfg["route_scale"], idx)


def routed(x, p, cfg, precision, first, held_weights):
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
    out, _ = lax.scan(one, jnp.zeros_like(x), (
        *held_weights, first + jnp.arange(wi.shape[0])))
    return out


def shared(x, p, precision):
    mm = functools.partial(reference._mm, precision=precision)
    return _swiglu(x, p["moe/shared/wi/kernel"], p["moe/shared/wg/kernel"],
                   p["moe/shared/wo/kernel"], mm)


def _block(x, p, cfg, precision, windowed, dense):
    eps = cfg["rms_norm_eps"]
    x = x + _norm(_attention(_norm(x, p["ln_attn/scale"], eps), p, cfg,
                             precision, windowed),
                  p["ln_attn_out/scale"], eps)
    h = _norm(x, p["ln_mlp/scale"], eps)
    if dense:
        mm = functools.partial(reference._mm, precision=precision)
        y = _swiglu(h, p["mlp/wi/kernel"], p["mlp/wg/kernel"],
                    p["mlp/wo/kernel"], mm)
    else:
        y = routed(h, p, cfg, precision, cfg["first_expert_held"],
                   (p["moe/experts/wi"], p["moe/experts/wg"],
                    p["moe/experts/wo"])) + shared(h, p, precision)
    return x + _norm(y, p["ln_mlp_out/scale"], eps)


def _row_loss_sum(params: dict, row, cfg: dict, precision: str):
    """One row of tokens [S]: its summed next-token cross entropy."""
    inputs, targets = row[:-1], row[1:]
    x = math.sqrt(cfg["hidden_size"]) * jnp.take(params["embed"], inputs,
                                                 axis=0)
    for i, kind in enumerate(_layer_types(cfg)):
        pre = f"block_{i}/"
        block = jax.checkpoint(functools.partial(
            _block, cfg=cfg, precision=precision,
            windowed=kind == _WINDOWED, dense=i < cfg["num_dense_layers"]))
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

def band_pairs(positions: int, window: int | None) -> int:
    """(query, key) pairs of one head of one row: the causal triangle with
    its diagonal, or the band of ``window`` keys a query."""
    if window is None or window >= positions:
        return positions * (positions + 1) // 2
    return window * (window + 1) // 2 + (positions - window) * window


def _pairs_by_kind(cfg: dict, positions: int) -> dict:
    kinds = _layer_types(cfg)
    return {_FULL: kinds.count(_FULL) * band_pairs(positions, None),
            _WINDOWED: kinds.count(_WINDOWED)
            * band_pairs(positions, cfg["sliding_window"])}


def _forward_flops(cfg: dict, rows: int, positions: int) -> float:
    """Every matmul of the equations above over ``rows`` rows of
    ``positions`` positions: attention at the pairs its mask leaves
    (``Q K^T`` and ``P V``: ``4 * head_dim`` a pair a head), the routed
    experts by the rows expected here under even routing.  The norms, the
    rotation, the softmax and the sigmoids are not matmuls and are not
    counted."""
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    t = rows * positions
    layers, n_dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    projections = 2 * t * d * hd * (3 * h + 2 * kv)      # q, gate, out; k, v
    pairs = sum(_pairs_by_kind(cfg, positions).values())
    ff = cfg["moe_intermediate_size"]
    routed_rows = (t * cfg["num_experts_per_tok"] * cfg["num_experts"]
                   / cfg["router_num_experts"])
    experts = (2 * t * d * cfg["router_num_experts"]
               + 3 * 2 * routed_rows * d * ff
               + 3 * 2 * t * d * ff * cfg["num_shared_experts"])
    dense = 3 * 2 * t * d * cfg["intermediate_size"]
    return (layers * projections + rows * h * 4 * pairs * hd
            + n_dense * dense + (layers - n_dense) * experts
            + 2 * t * d * cfg["vocab_size"])


def train_flops(cfg: dict, rows: int, row_tokens: int) -> float:
    """Model FLOPs of one train step: ``row_tokens - 1`` targets a row,
    backward twice the forward; recomputation, the tiles a kernel multiplies
    outside the band and the experts' padding never credited."""
    return 3.0 * _forward_flops(cfg, rows, row_tokens - 1)


def _flash_work(cfg: dict, rows_per_chip: int, positions: int, pairs: int,
                layers: int, act_bytes: int) -> dict:
    """``lib/flops.flash_train_work`` at this family's heads: forward ``4 *
    head_dim`` FLOPs a pair a query head, backward twice that; q, o, do, dq
    (six reads and writes) at the query heads, k, v, dk, dv (six) at the
    key/value heads, which is what a kernel that does not repeat K and V
    would move."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"flops": 3 * rows_per_chip * h * 4 * pairs * hd,
            "bytes": layers * rows_per_chip * positions * hd * act_bytes
            * 6 * (h + kv)}


def attention_work(cfg: dict, rows_per_chip: int, positions: int,
                   act_bytes: int = 2) -> dict:
    """All attention layers' work of one train step: the full layers'
    causal half and each windowed layer's band."""
    pairs = _pairs_by_kind(cfg, positions)
    return _flash_work(cfg, rows_per_chip, positions, sum(pairs.values()),
                       cfg["num_hidden_layers"], act_bytes)


def swa_work(cfg: dict, rows_per_chip: int, positions: int,
             act_bytes: int = 2) -> dict:
    """The windowed layers' work alone (``swa_roofline``): the band's pairs,
    whatever tiles a kernel multiplies."""
    return _flash_work(cfg, rows_per_chip, positions,
                       _pairs_by_kind(cfg, positions)[_WINDOWED],
                       _layer_types(cfg).count(_WINDOWED), act_bytes)
