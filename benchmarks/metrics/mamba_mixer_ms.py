"""Device time a step, mean over the chips, of the operations under the
``mamba_mixer`` scope: the Mamba-2 layers' norm, ``W_in``, convolution,
state-space scan, gated group norm and ``W_out``, forward, recomputed
forward and transposed."""

from ddbench import nemotron_scopes


def read(ctx):
    return nemotron_scopes.scope_ms(ctx, "mamba_mixer")
