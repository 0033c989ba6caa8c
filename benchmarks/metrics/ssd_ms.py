"""Device time a step, mean over the chips, of the operations under the
``ssd`` scope: the state-space scan between ``x, dt, B, C`` and ``y``,
forward, recomputed forward and transposed, whatever implements it."""

from ddbench import nemotron_scopes


def read(ctx):
    return nemotron_scopes.scope_ms(ctx, "ssd")
