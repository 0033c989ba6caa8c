"""Blocks the three flash kernels' grids visit over blocks that hold a live
pair of the sliding window, from the program's
``counters()["flash_geometry"]`` window calls: 1.0 where no block wholly
before the window (or after the diagonal) is a step."""

from ddbench import smallthinker_scopes


def read(ctx):
    return smallthinker_scopes.visited_over_live(ctx)
