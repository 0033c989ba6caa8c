"""Roofline share of the held ungated experts' grouped products: two
products a pair where the stem's reader counts SwiGLU's three
(``ddbench/nemotron_flops.py:expert_flops_bytes``), over the time under
``moe_experts``, whatever implements it."""

from ddbench import nemotron_scopes


def read(ctx):
    return nemotron_scopes.experts_roofline(ctx)
