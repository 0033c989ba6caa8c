"""Roofline share of the gated short convolution: 11 x 2048 x 2 B a token
and conv layer (``ddbench/lfm2_flops.py:short_conv_bytes``) over the HBM
peak, over the time under ``short_conv``, whatever implements it."""

from ddbench import lfm2_scopes


def read(ctx):
    return lfm2_scopes.short_conv_roofline(ctx)
