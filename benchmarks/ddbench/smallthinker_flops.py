"""Operations and bytes of the ``smallthinker_moe_lm`` family, computed from
shapes, from the sliding window's live pairs and, for the routed experts,
from what the router actually sent them.

``window_pairs`` is the window's own count a head: ``sum over i of min(i +
1, W)``, a causal layer's ``S (S + 1) / 2`` where ``W >= S``.
``flash_flops_bytes_per_step`` is what the three flash kernels must do for
the layers given, counted from the mask whatever implements it.
``step_flops`` is the work a step's loss depends on, for ``mfu``: forward +
backward = 3 x forward, recomputation under remat not counted (as
``flops.lm_flops_per_step``). The grouped products' own count is
``moe_flops.expert_flops_bytes`` (three matrices an expert, ReGLU's as
SwiGLU's), which reads the keys this family's ``job.config`` answers."""

from __future__ import annotations


def window_pairs(s: int, window=None) -> int:
    """(query, key) pairs a head that a causal call over ``s`` positions
    keeps under a sliding ``window`` (None: the causal ``s (s + 1) / 2``)."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def layer_windows(c: dict) -> list:
    """Each layer's window, None where the layer is causal."""
    return [c["sliding_window_size"] if w else None
            for w in c["sliding_window_layout"]]


def flash_flops_bytes_per_step(windows, b: int, heads: int, kv_heads: int,
                               s: int, head_dim: int, itemsize: int = 2):
    """What the three flash kernels (forward, dq, dkv) must do for one
    training step over the layers whose windows are ``windows`` (None:
    causal): per live pair and query head 18 d FLOPs
    (``flops.flash_flops_bytes_per_step``'s count: forward 2 matmuls, dq 3,
    dkv 4, 2 d each). Bytes: each kernel reads its operands and writes its
    results once over the ``s`` positions, q, o, do and dq at the query
    heads, k, v, dk and dv at the K/V heads, lse and delta as float32 a row
    and query head."""
    pairs = sum(window_pairs(s, w) for w in windows)
    flops = 18.0 * head_dim * pairs * b * heads
    rows = s * b * len(windows)
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
    return {"qkv": 2 * d * (nh + 2 * nkv) * hd, "o": 2 * nh * hd * d,
            "router": 2 * d * c["moe_num_primary_experts"]
            * c["expert_parallel"]["chips"],
            "expert": 6 * d * c["moe_ffn_hidden_size"]}


def step_flops(c: dict, b: int, s: int, pairs_held=None) -> float:
    """FLOPs one training step requires of this chip: every layer's
    projections, router and attention over its own pairs (the window's
    where it has one), the held experts' products and the head.
    ``pairs_held``: the (position, expert) pairs routed to the held
    experts, summed over the layers; default the expectation (every expert
    alike): positions x experts a position / chips a layer."""
    t = b * s
    per = layer_matmul_flops_per_position(c)
    windows = layer_windows(c)
    if pairs_held is None:
        pairs_held = len(windows) * t * c["moe_num_active_primary_experts"] \
            / c["expert_parallel"]["chips"]
    attention = 4.0 * c["head_dim"] * c["num_attention_heads"] * b * sum(
        window_pairs(s, w) for w in windows)
    fwd = t * len(windows) * (per["qkv"] + per["o"] + per["router"]) \
        + pairs_held * per["expert"] + attention \
        + 2.0 * t * c["hidden_size"] * c["vocab_size"]
    return 3.0 * fwd
