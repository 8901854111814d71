"""A second family, for the tests alone: the five names of
``lib/modules.py`` for configurations written with GPT-2's key names.  No
file of ``benchmarks/`` outside ``tests/data/`` knows of it; the harness
finds it from the ``model_type`` of ``tests/data/configs/toy.json``.

The program has one LM (``TransformerLM``), so the leaves are the dense
family's; what is the toy's own is how its keys are read, how its leaves
are drawn (flatter than the dense family's), and what it counts as a
step's work (the matmuls of the blocks and the head, no attention)."""

import math

from lib import reference


def _hf(cfg: dict) -> dict:
    """The plain dense reference reads HF's key names."""
    return {"hidden_size": cfg["n_embd"],
            "num_attention_heads": cfg["n_head"],
            "num_hidden_layers": cfg["n_layer"],
            "intermediate_size": cfg["n_inner"],
            "vocab_size": cfg["vocab_size"],
            "rms_norm_eps": cfg["layer_norm_epsilon"],
            "rope_theta": cfg["rope_theta"]}


def model_kwargs(cfg: dict, remat: bool) -> dict:
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
                n_layers=cfg["n_layer"], n_heads=cfg["n_head"],
                d_ff=cfg["n_inner"], max_seq=cfg["n_positions"],
                attn_impl="flash", remat=remat)


def leaf_moments(path: str, shape) -> tuple[float, float]:
    if len(shape) == 1:                 # norm scales
        return 1.0, 0.05
    if path == "embed":
        return 0.0, 0.05
    return 0.0, 0.5 / math.sqrt(math.prod(shape[:-1]))


def loss_and_grads(params, tokens, cfg, precision="f32"):
    return reference.loss_and_grads(params, tokens, _hf(cfg), precision)


def train_flops(cfg: dict, rows: int, row_tokens: int) -> float:
    d, ff = cfg["n_embd"], cfg["n_inner"]
    per_token = cfg["n_layer"] * (8 * d * d + 6 * d * ff) \
        + 2 * d * cfg["vocab_size"]
    return 3.0 * rows * (row_tokens - 1) * per_token


def attention_work(cfg: dict, rows_per_chip: int, positions: int) -> dict:
    return {"flops": 0.0, "bytes": 1.0}
