"""The ``ddstore:state_init`` phase: ``create_train_state``."""

from ddbench import scopes


def read(ctx):
    return scopes.phase_s(ctx, "ddstore:state_init")
