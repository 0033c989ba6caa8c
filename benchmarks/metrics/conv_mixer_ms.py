"""Device time a step, mean over the chips, of the operations under the
``conv_mixer`` scope: the conv layers' norm, ``W_in``, gated short
convolution and ``W_out``, forward, recomputed forward and transposed."""

from ddbench import lfm2_scopes


def read(ctx):
    return lfm2_scopes.scope_ms(ctx, "conv_mixer")
