"""Device time of the multi-token-prediction module in one traced step: the
union of the ``XLA Ops`` events whose result carries a dimension only the
module's arrays have.  Over rows of ``S`` tokens the model's inputs have
``S - 1`` positions and the module's ``S - 2``, so its arrays are ``[rows,
S - 2, ...]``, ``[rows * (S - 2), ...]`` where its expert layer flattens
the tokens, and ``[rows * (S - 2) * k]`` where it sorts their ``k``
choices; the main path's are ``S - 1`` wide (``lib/hybrid_names.py``'s
``gdn_event`` anchors on a state's shape the same way).  What that covers:
the module's embedding and norms, ``eh_proj``, its block's projections,
norms, rotation, router, dispatch and shared expert, its head and its cross
entropy, forward and backward.  What it leaves out: the block's flash and
grouped-matmul calls, whose results are padded to the kernels' blocks and
buffers (``flash.*`` read the flash calls among all the others), and the
weights' gradients, whose shapes are the weights'.  Nothing to read where
the configuration has no module or the trace no such event."""

import re

from lib import xplane

_RESULT = re.compile(r"^%[\w.\-]+ = (\(.*?\)|\S+) [\w\-]+\(")


def mtp_dims(cfg: dict, cell: dict) -> set:
    """The dimensions only the module's arrays have, for a cell's rows."""
    ahead = cell["row_tokens"] - 2
    tokens = cell["batch_per_chip"] * ahead
    return {ahead, tokens, tokens * cfg["num_experts_per_tok"]}


def is_mtp_event(name: str, dims: set) -> bool:
    """Whether an op event's result (its HLO text before the op's name)
    holds one of ``dims`` as a whole dimension."""
    m = _RESULT.match(name)
    return bool(m) and any(
        int(d) in dims for shape in re.findall(r"\[([\d,]*)\]", m.group(1))
        for d in shape.split(",") if d)


def read(record):
    cfg = record["config"]
    trace = record.get("trace")
    if not cfg.get("num_nextn_predict_layers") or not trace \
            or not trace.get("devices") or not trace.get("steps"):
        return None
    dims = mtp_dims(cfg, record["cell"])
    per_chip = [xplane.busy_union_ns([e for e in ev
                                      if is_mtp_event(e[0], dims)])
                for ev in trace["devices"].values()]
    ns = sum(per_chip) / len(per_chip)
    return ns / 1e6 / trace["steps"] if ns > 0 else None
