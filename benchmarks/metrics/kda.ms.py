"""Device time of the channel-wise delta rule (Kimi Delta Attention) in one
traced step: the union of the Mosaic calls named ``kda_*``
(``kda_chunk_fwd``, ``kda_chunk_bwd``: the chunk-local stage, one call a
KDA layer a pass) and of the ``while`` instructions that carry the rule's
state ``f32[rows, heads, head_dim, head_dim]`` over the chunks (forward,
recomputed forward, backward).  What it leaves out is the layout work
around them (padding, casts, the output's transpose: plain fusions under
the scope ``kda``, in ``tools/scope_dump.py``'s ``kda`` row).  Nothing to
read where the configuration has no such layers or the program no such
kernel (the parent of the PR that added them)."""

import re

from lib import hybrid_names


def kda_event(cfg: dict, rows_per_chip: int) -> str:
    linear = cfg["linear_attn_config"]
    state = "f32\\[{0},{1},{2},{2}\\]".format(
        rows_per_chip, linear["num_heads"], linear["head_dim"])
    return (rf'^%while[\w.\-]* = \(.*?{state}.*? while\('
            rf'|^%kda_\w+(\.\d+)? = .*custom_call_target="tpu_custom_call"')


def read(record):
    cfg = record["config"]
    if "kda_layers" not in cfg.get("linear_attn_config", {}):
        return None
    pattern = kda_event(cfg, record["cell"]["batch_per_chip"])
    trace = record.get("trace")
    # the loops alone are not the operator: without a kernel's call in the
    # trace there is nothing of that name to read
    if not trace or not any(
            re.search(r"^%kda_\w+", name) for events in
            trace.get("devices", {}).values() for name, *_ in events):
        return None
    return hybrid_names.union_ms_per_step(trace, pattern)
