"""The dense family (``"model_type": "olmo"``): pre-norm blocks of
multi-head attention with full rotary embedding and a SwiGLU MLP, head tied
to the embedding.  The five names ``lib/modules.py`` lists, bound to the
yardstick's dense arithmetic."""

from lib import flops, reference, weights


def model_kwargs(cfg: dict, remat: bool) -> dict:
    """The program's names for the configuration file's (HF) keys."""
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("hidden_size is not a multiple of the head count")
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                d_ff=cfg["intermediate_size"],
                max_seq=cfg["max_position_embeddings"],
                attn_impl="flash", remat=remat)


leaf_moments = weights.leaf_moments
loss_and_grads = reference.loss_and_grads
train_flops = flops.lm_train_flops
attention_work = flops.flash_train_work
