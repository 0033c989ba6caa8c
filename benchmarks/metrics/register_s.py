"""The ``ddstore:register`` phases of rank 0, summed: every collective
``DDStore.add``, shape exchange to last barrier."""

from ddbench import scopes


def read(ctx):
    return scopes.phase_s(ctx, "ddstore:register")
