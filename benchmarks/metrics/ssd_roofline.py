"""Roofline share of the state-space scan: the larger of its least bytes
over the HBM peak and the chunked form's matmul FLOPs over the bf16 peak
(``ddbench/nemotron_flops.py:ssd_flops_bytes``), over the time under
``ssd``, whatever implements it."""

from ddbench import nemotron_scopes


def read(ctx):
    return nemotron_scopes.ssd_roofline(ctx)
