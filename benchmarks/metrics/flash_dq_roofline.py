"""Roofline share of the flash dq kernel (``ddstore_flash_dq``): 6 d FLOPs per
live pair and head; see ``flash_fwd_roofline``."""

from ddbench import scopes


def read(ctx):
    return scopes.flash_roofline(ctx, "ddstore_flash_dq")
