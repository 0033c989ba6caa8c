"""Roofline share of the flash forward kernel, found in the trace by the name
the program gives it (``ddstore_flash_fwd``): 4 d FLOPs per live pair and
head over the bf16 peak (or its bytes over the HBM peak, the larger) over the
kernel's time, summed over the chips."""

from ddbench import scopes


def read(ctx):
    return scopes.flash_roofline(ctx, "ddstore_flash_fwd")
