"""Operations and bytes, computed from shapes, and the table of peaks.

``lm_flops_per_step`` is copied from ``bench.py:_lm_flops_per_step`` (listed in
PERF.md's Open questions for a later PR to delete there)."""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. An unknown device is an error:
    a share of an assumed peak is not a measurement."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"benchmarks/peaks.json (has {sorted(table)}); add it "
                       f"with its published peaks and their source")
    return table[device_kind]


def lm_flops_per_step(vocab: int, dim: int, layers: int, b: int, s: int
                      ) -> float:
    """Forward + backward FLOPs the model requires: matmuls (qkv 6Td^2 + proj
    2Td^2 + mlp 16Td^2 per layer, head 2TdV) + causal attention (2 b s^2 d per
    layer); backward = 2 x forward. Recomputation does not count."""
    t = b * s
    fwd = layers * (24 * t * dim * dim + 2 * b * s * s * dim) \
        + 2 * t * dim * vocab
    return 3.0 * fwd


def flash_flops_bytes_per_step(layers: int, b: int, heads: int, s: int,
                               head_dim: int, itemsize: int = 2):
    """What the three flash kernels (forward, dq, dkv) must do for one training
    step of causal attention over whole sequences of ``s``, however the
    sequence is split over chips. Per (query, key) pair with key <= query and
    per head: forward 2 matmuls (q k^T, p v), dq 3 (q k^T again, do v^T,
    ds k), dkv 4 (q k^T again, do v^T, p^T do, ds^T q), 2 d FLOPs each: 18 d.
    Bytes: each kernel reads its operands and writes its results once (q, k,
    v, o, do, dq, dk, dv in ``itemsize``; lse and delta as float32 per row)."""
    pairs = s * (s + 1) // 2
    flops = 18.0 * head_dim * pairs * b * heads * layers
    rows = b * heads * s * layers
    fwd = rows * (4 * head_dim * itemsize + 4)
    dq = rows * (5 * head_dim * itemsize + 8)
    dkv = rows * (6 * head_dim * itemsize + 8)
    return flops, float(fwd + dq + dkv)
