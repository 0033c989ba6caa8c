"""The one general generator: builds a rank's shard from ``--seed`` and the
traffic file's parameters, in chunks, in the type the store serves.

One row kind so far, a pure function of ``(seed, global row id)``:

* ``tokens`` -- ``seq + 1`` ids per window from ``(seed, 2, window id)``,
  Zipf-like with exponent 1: an octave ``e`` uniform in [0, log2 vocab), then
  uniform inside ``[2**e - 1, 2**(e+1) - 2]``, so P(id = k) ~ 1/k. Integer
  arithmetic only: the reference reproduces it bit for bit. A window's
  targets are its ids shifted by one.

``reference/rows.py`` holds the plain per-row form the loader's output is
compared with; ``tests/test_rows.py`` holds the two to each other.
"""

from __future__ import annotations

import numpy as np


def _octaves(vocab: int) -> int:
    n = int(vocab).bit_length() - 1
    if not 1 <= n <= 16:
        raise ValueError(f"vocab {vocab} outside [2, 2**17)")
    return n


def token_shard(seed: int, first_row: int, nrows: int, seq: int,
                vocab: int):
    """(tokens, next tokens), both (nrows, seq) int32."""
    n_oct = np.uint32(_octaves(vocab))
    ids = np.empty((nrows, seq + 1), np.int32)
    one = np.uint32(1)
    for i in range(nrows):
        r = np.random.default_rng((seed, 2, first_row + i)).integers(
            0, 1 << 32, seq + 1, dtype=np.uint32)
        e = ((r >> np.uint32(16)) * n_oct) >> np.uint32(16)
        span = (one << e) - one
        ids[i] = span + (r & np.uint32(0xFFFF) & span)
    return np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])
