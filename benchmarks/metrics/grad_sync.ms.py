"""Device time of one traced step in which an all-reduce was running or in
flight: the union of the all-reduce events of the ``XLA Ops`` line (start to
done where they are asynchronous pairs; ``lib/collectives.py``), mean over
the chips.  On one chip there is none and nothing to read."""

from lib import collectives


def read(record):
    return collectives.all_reduce_ms_per_step(record.get("trace"))
