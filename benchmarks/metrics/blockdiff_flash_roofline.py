"""Roofline share of the flash kernels under the block-diffusion mask: the
mask's live pairs x 18 x 128 FLOPs a head over the bf16 peak, or the
kernels' bytes over the HBM peak, the larger
(``ddbench/sdar_flops.py:flash_flops_bytes_per_step``: work counted from the
mask, whatever implements it), over the three kernels' time."""

from ddbench import sdar_scopes


def read(ctx):
    return sdar_scopes.flash_roofline(ctx)
