"""Device time a step, mean over the chips, of the three flash kernels
(``ddstore_flash_fwd``, ``_dq``, ``_dkv``, by name, not every Mosaic call)
under the block-diffusion mask."""

from ddbench import sdar_scopes


def read(ctx):
    return sdar_scopes.flash_ms(ctx)
