"""Operations and bytes of the ``nemotron_h_lm`` family, computed from
shapes and, for the routed experts, from what the router actually sent them.

``step_flops`` is the work a training step requires, for ``mfu``: forward +
backward = 3 x forward, recomputation under remat not counted (as the other
families' counts). It counts matmuls, the held pairs as routed, causal
attention at 4 x heads x head width a pair, and the state-space scan at its
recurrence's 4 N P a head and token; the convolution, the norms and the
gates are left out, so it reads low and never high.
``ssd_flops_bytes`` is the least a realisation of the scan between its
operands and its result must do, for ``ssd_roofline``;
``expert_flops_bytes`` the grouped products' of ungated experts, two
products a pair, for ``moe_experts_roofline.nemotron3``."""

from __future__ import annotations


def layer_flops_per_token(c: dict) -> dict:
    """Forward FLOPs a token of one layer's parts, from the configuration's
    widths (2 x rows x columns a product)."""
    d = c["hidden_size"]
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    mh, mp, n, g = (c["mamba_num_heads"], c["mamba_head_dim"],
                    c["ssm_state_size"], c["n_groups"])
    inner = mh * mp
    return {"mamba": 2 * d * (2 * inner + 2 * g * n + mh) + 2 * inner * d
            + 4 * n * mp * mh,
            "attention": 2 * d * (nh + 2 * nkv) * hd + 2 * nh * hd * d,
            "router": 2 * d * c["n_routed_experts"]
            * c["expert_parallel"]["chips"],
            "shared": 4 * d * c["moe_shared_expert_intermediate_size"],
            "expert": 4 * d * c["moe_intermediate_size"]}


def attention_flops(c: dict, b: int, s: int) -> float:
    """Forward FLOPs of one causal attention call: q k^T and p v at the
    head width over the pairs with key <= query, every query head (what
    ``flops.flash_flops_bytes_per_step`` counts, 4 d a pair)."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"] \
        * (s * (s + 1) // 2) * b


def step_flops(c: dict, b: int, s: int, pairs_held=None) -> float:
    """FLOPs one training step requires of this chip. ``pairs_held``: the
    (token, expert) pairs routed to the held experts, summed over the
    expert layers; default the expectation (every expert alike): tokens x
    experts a token / chips a layer."""
    t = b * s
    per = layer_flops_per_token(c)
    pattern = c["hybrid_override_pattern"]
    n_m, n_e, n_a = (pattern.count(k) for k in "ME*")
    if pairs_held is None:
        pairs_held = n_e * t * c["num_experts_per_tok"] \
            / c["expert_parallel"]["chips"]
    fwd = t * (n_m * per["mamba"] + n_a * per["attention"]
               + n_e * (per["router"] + per["shared"])) \
        + pairs_held * per["expert"] \
        + n_a * attention_flops(c, b, s) \
        + 2.0 * t * c["hidden_size"] * c["vocab_size"]
    return 3.0 * fwd


def ssd_flops_bytes(c: dict, tokens: int, itemsize: int = 2):
    """``(FLOPs, bytes)`` the state-space scans of one training step need
    at the least, between ``x, dt, B, C`` and ``y`` (the convolution before
    and the gated norm after are not the scan's), over the ``M`` layers.
    Bytes: a token and layer reads ``x`` (H P), ``B`` and ``C`` (G N each)
    and ``dt`` (H) and writes ``y`` forward; reads those and ``dy`` and
    writes ``dx, dB, dC, ddt`` backward; no intermediate counted, all in
    the compute type. FLOPs: the chunked form's four products at the
    published ``chunk_size`` Q, the two within a chunk over their lower
    triangle alone ((Q + 1) / 2 keys a query: ``C B^T`` 2 G N each, the
    decayed matrix on ``dt x`` 2 H P each), the chunk's state and ``C S``
    2 H P N a token each; backward twice the forward. The forward computed
    again under remat is time, not work."""
    mh, mp, n, g, q = (c["mamba_num_heads"], c["mamba_head_dim"],
                       c["ssm_state_size"], c["n_groups"], c["chunk_size"])
    layers = c["hybrid_override_pattern"].count("M")
    inner = mh * mp
    operands = inner + 2 * g * n + mh
    moved = (operands + inner) + (operands + inner) + operands
    fwd = (q + 1) * (g * n + inner) + 4 * inner * n
    return (3.0 * fwd * tokens * layers,
            float(moved * itemsize * tokens * layers))


def expert_flops_bytes(c: dict, pairs_held: float, moe_layers: int,
                       itemsize: int = 2):
    """``(FLOPs, bytes)`` the grouped products of the held ungated experts
    need for the steps that routed ``pairs_held`` pairs to them (summed
    over layers and steps; ``moe_layers`` = expert layers x steps): 2
    products a pair forward, 4 backward, 2 x d x w FLOPs each (where
    ``moe_flops.expert_flops_bytes`` counts SwiGLU's 3 and 6); the held
    experts' two matrices read three times (forward, d-input, and written
    once as d-weight) and the sorted rows in and out of each product."""
    d, w = c["hidden_size"], c["moe_intermediate_size"]
    flops = 3.0 * 4 * d * w * pairs_held
    weights = 3.0 * moe_layers * c["n_routed_experts"] * 2 * d * w * itemsize
    # a pair's rows: x in, up out, h in, y out, and their cotangents
    rows = 2.0 * pairs_held * (2 * d + 2 * w) * itemsize
    return flops, weights + rows
