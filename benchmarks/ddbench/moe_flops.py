"""Operations and bytes of the ``mla_moe_lm`` family, computed from shapes
and, for the routed experts, from what the router actually sent them.

``step_flops`` is the work a training step requires, for ``mfu``: forward +
backward = 3 x forward, recomputation under remat not counted (as
``flops.lm_flops_per_step``). ``expert_flops_bytes`` is the grouped
products' alone, from the load vectors of the traced steps."""

from __future__ import annotations


def layer_matmul_flops_per_token(c: dict) -> dict:
    """Forward matmul FLOPs a token of one layer's parts, from the
    configuration's widths (2 x rows x columns a product)."""
    d, nh = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    mla = 2 * (d * c["q_lora_rank"] + c["q_lora_rank"] * nh * qk
               + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
               + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])
               + nh * c["v_head_dim"] * d)
    expert = 6 * d * c["moe_intermediate_size"]
    return {"mla": mla, "dense_mlp": 6 * d * c["intermediate_size"],
            "router": 2 * d * c["n_routed_experts"]
            * c["expert_parallel"]["chips"],
            "shared": expert * c["n_shared_experts"], "expert": expert}


def attention_flops(c: dict, b: int, s: int) -> float:
    """Forward FLOPs of one causal attention call: q k^T at the query/key
    width and p v at the value width, over the pairs with key <= query
    (what ``flops.flash_flops_bytes_per_step`` counts, 4 d a pair)."""
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return 2.0 * (qk + c["v_head_dim"]) * (s * (s + 1) // 2) * b \
        * c["num_attention_heads"]


def step_flops(c: dict, b: int, s: int, pairs_held=None) -> float:
    """FLOPs one training step requires of this chip. ``pairs_held``: the
    (token, expert) pairs routed to the held experts, summed over the
    expert layers, MTP's included; default the expectation (every expert
    alike): tokens x experts a token / chips a layer."""
    t = b * s
    per = layer_matmul_flops_per_token(c)
    dense = c["first_k_dense_replace"]
    moe_layers = c["num_hidden_layers"] - dense \
        + c["num_nextn_predict_layers"]
    blocks = c["num_hidden_layers"] + c["num_nextn_predict_layers"]
    if pairs_held is None:
        pairs_held = moe_layers * t * c["num_experts_per_tok"] \
            / c["expert_parallel"]["chips"]
    heads = 1 + c["num_nextn_predict_layers"]
    fwd = t * (blocks * per["mla"] + dense * per["dense_mlp"]
               + moe_layers * (per["router"] + per["shared"])) \
        + pairs_held * per["expert"] \
        + blocks * attention_flops(c, b, s) \
        + heads * 2.0 * t * c["hidden_size"] * c["vocab_size"] \
        + c["num_nextn_predict_layers"] * 2.0 * t * 2 * c["hidden_size"] ** 2
    return 3.0 * fwd


def expert_flops_bytes(c: dict, pairs_held: float, moe_layers: int,
                       itemsize: int = 2):
    """``(FLOPs, bytes)`` the grouped products of the held experts need for
    the steps that routed ``pairs_held`` pairs to them (summed over layers
    and steps; ``moe_layers`` = expert layers x steps): 3 products a pair
    forward, 6 backward, 2 x 2048 x 1536 FLOPs each; the held experts'
    weights read three times (forward, d-input, and written once as
    d-weight) and the sorted rows in and out of each product."""
    d, w = c["hidden_size"], c["moe_intermediate_size"]
    flops = 3.0 * 6 * d * w * pairs_held
    held = c["n_routed_experts"]
    weights = 3.0 * moe_layers * held * 3 * d * w * itemsize
    # a pair's rows: x in (twice), gate and up out, h in, y out, and their
    # cotangents the other way
    rows = 2.0 * pairs_held * (3 * d + 3 * w) * itemsize
    return flops, weights + rows
