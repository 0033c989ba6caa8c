"""Key blocks the three flash kernels' grids visit over key blocks that hold
a live pair of the block-diffusion mask, from the program's
``counters()["flash_geometry"]``: 1.0 where dead blocks are skipped, near 4
where the whole (2 S)^2 is stepped over and masked."""

from ddbench import sdar_scopes


def read(ctx):
    return sdar_scopes.visited_over_live(ctx)
