"""Device time of the gated delta rule's loops in one traced step: the
``while`` instructions that carry the rule's state over the chunks (forward,
recomputed forward, backward), or Mosaic calls named ``gdn_*`` once a
kernel exists.  The loops alone: the part of the operator that is
sequential in the chunks, a quarter of its time while it is plain JAX; the
chunk-local part before them has no name on the ``XLA Ops`` line and is in
``tools/scope_dump.py``'s ``gdn`` row (``lib/hybrid_names.py``, PERF.md
section 3).  Nothing to read where the configuration has no linear
layers."""

from lib import hybrid_names


def read(record):
    return hybrid_names.gdn_ms(record)
