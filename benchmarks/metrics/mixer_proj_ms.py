"""Device time a step, mean over the chips, of the mixers' input and output
projections (innermost scope ``mix_in`` or ``mix_out``), every pass,
whatever the mixer: attention, latent attention, short convolution,
Mamba-2."""

from ddbench import passes


def read(ctx):
    return passes.kind_ms(ctx, "mix_in", "mix_out")
