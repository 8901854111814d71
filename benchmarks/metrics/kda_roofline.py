"""Least time the chip could take for the channel-wise delta rule of a step
/ device time of its kernels and loops (``kda.ms``).

The work is the family's ``kda_rule_work``: the recurrence's FLOPs (``6 *
Dk * Dv`` a token a head, backward twice the forward) and the bytes of q,
k, v, g at its key channels, beta, o and their cotangents once in float32;
bytes bound it.  The chunkwise form's own extra matmuls, recomputation and
padding are not credited, so it cannot pass 100%."""

import os

from lib import hybrid_names, modules

_kda_ms = modules.load_file(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "kda.ms.py"),
    "metrics")


def read(record):
    return hybrid_names.roofline_pct(record, _kda_ms.read(record),
                                     "kda_rule_work")
