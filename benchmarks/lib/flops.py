"""Operations and bytes the algorithm needs, from shapes alone.

The LM arithmetic is a copy of ``dtdl_tpu/obs/goodput.py``
(``lm_forward_flops`` / ``lm_train_flops``): matmuls only, causal
attention at the computed half, backward at twice the forward, recompute
(``remat``, the flash backward's second QK^T) never credited — so a share
of the chip's peak built on it cannot pass 100%.  A test pins the two
equal; the original is listed in PERF.md for a later PR to delete.

``cfg`` is a configuration file's dict (HF key names).
"""

from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return d, h, d // h, cfg["intermediate_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]


def lm_forward_flops(cfg: dict, batch: int, positions: int) -> float:
    d, h, hd, ff, layers, vocab = _dims(cfg)
    t = positions
    qkvo = 4 * 2 * batch * t * d * (h * hd)
    attn = 2 * 2 * batch * h * t * t * hd * 0.5
    mlp = 3 * 2 * batch * t * d * ff
    head = 2 * batch * t * d * vocab
    return layers * (qkvo + attn + mlp) + head


def lm_train_flops(cfg: dict, batch: int, row_tokens: int) -> float:
    """One train step on rows of ``row_tokens`` tokens: the step predicts
    ``row_tokens - 1`` next tokens, forward + 2x backward."""
    return 3.0 * lm_forward_flops(cfg, batch, row_tokens - 1)


def flash_train_work(cfg: dict, batch: int, positions: int,
                     act_bytes: int = 2) -> dict:
    """Attention work of one train step, all layers: FLOPs and HBM bytes.

    Forward 4*B*H*S^2*D/2 (QK^T and PV, causal half), backward twice that
    (dQ, dK, dV and dP; the recomputed QK^T is not credited).  A forward
    that ``remat`` runs again in the backward pass (a block whose checkpoint
    plan keeps no ``flash_out``) is not credited either.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o,
    do and writes dq, dk, dv — 12 tensors of B*H*S*D activations.  Shapes
    only: the same whatever kernel ran.
    """
    d, h, hd, _, layers, _ = _dims(cfg)
    fwd = 4 * batch * h * positions * positions * hd * 0.5
    tensor = batch * h * positions * hd * act_bytes
    return {"flops": layers * 3 * fwd, "bytes": layers * 12 * tensor}


def roofline_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which peak bounds it."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
