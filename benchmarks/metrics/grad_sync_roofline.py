"""Least time the interconnect could take for the gradient all-reduce of
a step / device time of a traced step's all-reduce events.

The work is reckoned from the parameter shapes alone: every parameter's
float32 gradient, 2 (n - 1) / n of those bytes sent by each chip
(``lib/collectives.py:ring_all_reduce_bytes``), over the chip's published
interconnect peak (``lib/peaks.py``); it does not depend on how the
compiler buckets or schedules them."""

import math

from lib import collectives, peaks


def read(record):
    ms = collectives.all_reduce_ms_per_step(record.get("trace"))
    if ms is None:
        return None
    gradient_bytes = 4 * sum(math.prod(s) for s in record["shapes"].values())
    least = collectives.ring_all_reduce_bytes(
        gradient_bytes, record["device"]["count"]) / (
        peaks.peaks_for(record["device"]["kind"])["ici_bits_per_s"] / 8)
    return 100.0 * least / (ms / 1e3)
