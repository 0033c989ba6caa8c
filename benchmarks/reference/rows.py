"""Plain reference for the store's contents: any row from ``(seed, global
row id)`` with numpy alone, one row at a time, independent of the store, the
loader and the chunked generator in ``ddbench/rows.py``. ``correct`` compares
what the loader delivered to the device, and what ``ds.fetch`` returns, with
these rows byte for byte."""

from __future__ import annotations

import numpy as np


def token_row(seed: int, row: int, seq: int, vocab: int):
    """(tokens, next tokens) of one window, each ``seq`` int32."""
    n_oct = int(vocab).bit_length() - 1
    r = np.random.default_rng((seed, 2, int(row))).integers(
        0, 1 << 32, seq + 1, dtype=np.uint32).astype(np.int64)
    e = ((r >> 16) * n_oct) >> 16
    span = (1 << e) - 1
    ids = (span + (r & 0xFFFF & span)).astype(np.int32)
    return ids[:-1], ids[1:]


def token_rows(seed: int, rows, seq: int, vocab: int):
    pairs = [token_row(seed, r, seq, vocab) for r in rows]
    return (np.stack([p[0] for p in pairs]), np.stack([p[1] for p in pairs]))
