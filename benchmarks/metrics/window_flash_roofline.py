"""Roofline share of the flash kernels under the ``window`` scope: the
window's live pairs, sum of min(i + 1, W), x 18 x 128 FLOPs a head and
windowed layer over the bf16 peak, or the kernels' bytes (K/V at their own
heads) over the HBM peak, the larger
(``ddbench/smallthinker_flops.py:flash_flops_bytes_per_step``: work counted
from the window, whatever implements it), over the three kernels' time
there."""

from ddbench import smallthinker_scopes


def read(ctx):
    return smallthinker_scopes.flash_roofline(ctx)
