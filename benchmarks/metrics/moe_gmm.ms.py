"""Device time of the held experts' grouped matmuls in one traced step: the
events ``%moe_gmm.<n>`` and ``%moe_tgmm.<n>`` with ``tpu_custom_call``
(``lib/hybrid_names.py``), the kernels' ``pallas_call(name=)``: three
matmuls an expert layer forward, again where the block is recomputed, and
six backward."""

from lib import hybrid_names


def read(record):
    return hybrid_names.moe_gmm_ms(record)
