"""Device time a step, mean over the chips, of the operations under the
``mamba_conv`` scope: the four biased taps and the silu over ``xBC``,
forward, recomputed forward and transposed, whatever implements them."""

from ddbench import nemotron_scopes


def read(ctx):
    return nemotron_scopes.scope_ms(ctx, "mamba_conv")
