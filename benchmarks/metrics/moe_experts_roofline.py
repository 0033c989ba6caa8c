"""Roofline share of the held experts' grouped products: FLOPs of the pairs
the traced steps routed to them (``ddbench/moe_flops.py``) over the bf16
peak, or their bytes over the HBM peak, the larger, over the time under
``moe_experts``, whatever implements it."""

from ddbench import moe_scopes


def read(ctx):
    return moe_scopes.experts_roofline(ctx)
