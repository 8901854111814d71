"""The one general generator of training traffic.

A cell's traffic file gives ``batch_per_chip``, ``row_tokens`` and the
``distribution`` of token ids; the program only ever receives the batches.
Batch ``index`` of seed ``seed`` is a pure function of the two, so no batch
repeats inside a window and every seed sees the same shapes.
"""

from __future__ import annotations

import numpy as np


def batch_tokens(seed: int, index: int, rows: int, row_tokens: int,
                 vocab: int, distribution: str = "uniform") -> np.ndarray:
    """int32 [rows, row_tokens]; every row differs (checked by the caller's
    tests, true with overwhelming odds for uniform ids)."""
    rng = np.random.default_rng([int(seed), int(index)])
    if distribution == "uniform":
        return rng.integers(0, vocab, (rows, row_tokens), dtype=np.int32)
    if distribution == "zipf":
        # ids by rank, exponent 1: the shape of natural text's unigrams
        p = 1.0 / np.arange(1, vocab + 1)
        return rng.choice(vocab, (rows, row_tokens),
                          p=p / p.sum()).astype(np.int32)
    raise ValueError(f"unknown token distribution {distribution!r}")
