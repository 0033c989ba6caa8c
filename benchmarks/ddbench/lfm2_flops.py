"""Operations and bytes of the ``lfm2_moe_lm`` family, computed from shapes
and, for the routed experts, from what the router actually sent them.

``step_flops`` is the work a training step requires, for ``mfu``: forward +
backward = 3 x forward, recomputation under remat not counted (as
``flops.lm_flops_per_step`` and ``moe_flops.step_flops``).
``short_conv_bytes`` is the least traffic of the gated short convolution
between its two projections, for ``short_conv_roofline``. The grouped
products' own count is ``moe_flops.expert_flops_bytes``, which reads the
keys this family's ``job.config`` answers."""

from __future__ import annotations


def layer_matmul_flops_per_token(c: dict) -> dict:
    """Forward FLOPs a token of one layer's parts, from the configuration's
    widths (2 x rows x columns a product; the convolution's taps and gates
    2 a multiply-add or product and channel)."""
    d, nh, nkv = (c["hidden_size"], c["num_attention_heads"],
                  c["num_key_value_heads"])
    hd = d // nh
    return {"conv": 2 * (d * 3 * d + d * d)
            + 2 * d * (c["conv_L_cache"] + 2),
            "attention": 2 * (d * (nh + 2 * nkv) * hd + nh * hd * d),
            "dense_mlp": 6 * d * c["intermediate_size"],
            "router": 2 * d * c["num_experts"] * c["expert_parallel"]["chips"],
            "expert": 6 * d * c["moe_intermediate_size"]}


def attention_flops(c: dict, b: int, s: int) -> float:
    """Forward FLOPs of one causal attention call: q k^T and p v at the
    head width over the pairs with key <= query, every query head (what
    ``flops.flash_flops_bytes_per_step`` counts, 4 d a pair)."""
    return 4.0 * c["hidden_size"] * (s * (s + 1) // 2) * b


def step_flops(c: dict, b: int, s: int, pairs_held=None) -> float:
    """FLOPs one training step requires of this chip. ``pairs_held``: the
    (token, expert) pairs routed to the held experts, summed over the
    expert layers; default the expectation (every expert alike): tokens x
    experts a token / chips a layer."""
    t = b * s
    per = layer_matmul_flops_per_token(c)
    kinds = c["layer_types"]
    dense = c["num_dense_layers"]
    moe_layers = len(kinds) - dense
    if pairs_held is None:
        pairs_held = moe_layers * t * c["num_experts_per_tok"] \
            / c["expert_parallel"]["chips"]
    n_attn = sum(k == "full_attention" for k in kinds)
    fwd = t * (kinds.count("conv") * per["conv"] + n_attn * per["attention"]
               + dense * per["dense_mlp"] + moe_layers * per["router"]) \
        + pairs_held * per["expert"] \
        + n_attn * attention_flops(c, b, s) \
        + 2.0 * t * c["hidden_size"] * c["vocab_size"]
    return 3.0 * fwd


def short_conv_bytes(c: dict, tokens: int, itemsize: int = 2) -> float:
    """The least bytes the gated short convolutions of one training step
    move, gates and taps alone (between ``W_in`` and ``W_out``): a token
    and layer reads ``Bg, Cg, u`` and writes ``y`` forward (4 C), reads
    ``Bg, Cg, u, dy`` and writes ``dBg, dCg, du`` backward (7 C); the taps
    and their gradient are a few KB. The forward recomputed under remat is
    time, not work."""
    return 11.0 * c["hidden_size"] * itemsize * tokens \
        * list(c["layer_types"]).count("conv")
