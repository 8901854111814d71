"""Least time the chip could take for the delta rule's recurrence of a
step / device time of its loops (``gdn.ms``): **the loops' share only**.

The work is the family's ``delta_rule_work``: the whole recurrence's FLOPs
(``6 * Dk * Dv`` a token a value head, backward twice the forward) and the
bytes of q, k, v, g, beta, o and their cotangents in float32; bytes bound
it.  The time is the loops' alone, a quarter of the operator while it is
plain JAX (``lib/hybrid_names.py``): the chunk-local part before the loops
is in neither, so this reads about four times the operator's own share
(PERF.md section 3).  The chunkwise form's own extra matmuls,
recomputation and padding are not credited, so it cannot pass 100%."""

from lib import hybrid_names


def read(record):
    return hybrid_names.roofline_pct(record, hybrid_names.gdn_ms(record),
                                     "delta_rule_work")
