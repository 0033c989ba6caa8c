"""Operations and bytes of the ``sdar_moe_lm`` family, computed from shapes,
from the block-diffusion mask's live pairs and, for the routed experts, from
what the router actually sent them.

A training step takes ``b`` windows of ``s`` tokens; the model runs ``2 s``
positions a window, ``[noised ; clean]``. ``live_pairs`` is the mask's own
count a head and window. ``flash_flops_bytes_per_step`` is what the three
flash kernels must do for it, every layer alike, counted from the mask
whatever implements it. ``step_flops`` is the work a step's loss depends on,
for ``mfu``: forward + backward = 3 x forward, recomputation under remat not
counted (as ``flops.lm_flops_per_step``). The grouped products' own count is
``moe_flops.expert_flops_bytes`` (three matrices an expert), which reads the
keys this family's ``job.config`` answers."""

from __future__ import annotations


def live_pairs(window: int, length: int) -> int:
    """(query, key) pairs the block-diffusion mask keeps, a head and
    window: noised on its own block ``s L``, noised on the clean blocks
    before ``L^2 n (n - 1) / 2``, clean on clean ``L^2 n (n + 1) / 2`` with
    ``n = s / L`` blocks: ``s^2 + s L`` of the ``(2 s)^2``."""
    n = window // length
    return window * length + length * length * (
        n * (n - 1) // 2 + n * (n + 1) // 2)


def clean_on_clean_pairs(window: int, length: int) -> int:
    """Of ``live_pairs``, those whose query is clean."""
    n = window // length
    return length * length * n * (n + 1) // 2


def flash_flops_bytes_per_step(layers: int, b: int, heads: int,
                               kv_heads: int, window: int, length: int,
                               head_dim: int, itemsize: int = 2):
    """What the three flash kernels (forward, dq, dkv) must do for one
    training step under the mask: per live pair and query head 18 d FLOPs
    (``flops.flash_flops_bytes_per_step``'s count: forward 2 matmuls, dq 3,
    dkv 4, 2 d each). Bytes: each kernel reads its operands and writes its
    results once over the ``2 window`` positions, q, o, do and dq at the
    query heads, k, v, dk and dv at the K/V heads, lse and delta as float32
    a row and query head."""
    flops = 18.0 * head_dim * live_pairs(window, length) * b * heads * layers
    rows = 2 * window * b * layers
    wide, thin = rows * heads * head_dim * itemsize, \
        rows * kv_heads * head_dim * itemsize
    stats = rows * heads * 4
    fwd = 2 * wide + 2 * thin + stats              # q k v -> o, lse
    dq = 3 * wide + 2 * thin + 2 * stats           # q k v do lse delta -> dq
    dkv = 2 * wide + 4 * thin + 2 * stats          # q k v do ... -> dk dv
    return flops, float(fwd + dq + dkv)


def layer_matmul_flops_per_position(c: dict) -> dict:
    """Forward FLOPs a position of one layer's parts, from the
    configuration's widths (2 x rows x columns a product)."""
    d, nh, nkv, hd = (c["hidden_size"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"])
    return {"q": 2 * d * nh * hd, "kv": 2 * d * 2 * nkv * hd,
            "o": 2 * nh * hd * d,
            "router": 2 * d * c["num_experts"] * c["expert_parallel"]["chips"],
            "expert": 6 * d * c["moe_intermediate_size"]}


def step_flops(c: dict, b: int, s: int, pairs_held=None) -> float:
    """FLOPs one training step's loss depends on, on this chip: ``2 s``
    positions a window through every layer and ``s`` rows (the noised
    half's) through the head. What nothing reads is left out: the last
    layer's clean half feeds no loss term beyond its keys and values, so
    its query and output projections, its attention rows (the clean-on-
    clean pairs), its router and its experts are not counted there (the
    program computes them: every layer runs alike). ``pairs_held``: the
    (position, expert) pairs routed to the held experts, a layer (a
    sequence, one entry a layer); default the expectation (every expert
    alike): positions x experts a position / chips."""
    layers, length = c["num_hidden_layers"], c["block_length"]
    per = layer_matmul_flops_per_position(c)
    if pairs_held is None:
        pairs_held = [2 * b * s * c["num_experts_per_tok"]
                      / c["expert_parallel"]["chips"]] * layers
    pairs_held = list(pairs_held)
    query_side = per["q"] + per["o"] + per["router"]
    attention = 4.0 * c["head_dim"] * c["num_attention_heads"] * b
    fwd = (2 * layers * per["kv"] + (2 * layers - 1) * query_side) * b * s \
        + per["expert"] * (sum(pairs_held[:-1]) + 0.5 * pairs_held[-1]) \
        + attention * (layers * live_pairs(s, length)
                       - clean_on_clean_pairs(s, length)) \
        + 2.0 * b * s * c["hidden_size"] * c["vocab_size"]
    return 3.0 * fwd
