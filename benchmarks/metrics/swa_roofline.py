"""Least time the chip could take for the windowed layers' attention of a
step / device time of the windowed flash kernels (``swa.ms``).

The work is the family's ``swa_work``: the band's pairs alone (``4 *
head_dim`` FLOPs a pair a query head forward, backward twice that) and the
bytes of q, o, do, dq at the query heads and k, v, dk, dv at the key/value
heads, at the chip's peaks as ``lib/flops.py:roofline_seconds`` reckons.
The tiles a kernel multiplies outside the band, the recomputed ``Q K^T`` of
the backward pass and K/V repeated to the query heads are not credited, so
it cannot pass 100%."""

import os

from lib import hybrid_names, modules

_swa_ms = modules.load_file(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "swa.ms.py"),
    "metrics")


def read(record):
    return hybrid_names.roofline_pct(record, _swa_ms.read(record),
                                     "swa_work")
