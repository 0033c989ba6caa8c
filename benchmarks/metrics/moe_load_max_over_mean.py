"""Largest over mean number of tokens routed to one of the held experts,
from the load vectors the traced steps returned beside their losses."""

from ddbench import moe_scopes


def read(ctx):
    return moe_scopes.load_max_over_mean(ctx)
