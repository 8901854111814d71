"""Least time the chip could take for the held experts' matmuls of a step
/ device time of the grouped-matmul kernels (``moe_gmm.ms``).

The work is the family's ``expert_matmul_work``: the rows expected here
under even routing, the held weights read once a pass and their gradient
written once; padding to the buffer's rows and recomputation show as lost
share, so nothing can read over 100%."""

from lib import hybrid_names


def read(record):
    return hybrid_names.roofline_pct(record, hybrid_names.moe_gmm_ms(record),
                                     "expert_matmul_work")
