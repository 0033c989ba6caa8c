"""Device time a step, mean over the chips, of the operations under the
``moe_experts`` scope: the grouped products of the held experts and their
SwiGLU. XLA's ragged-dot kernels, which carry its name and not the program's
scope, are counted by that name."""

from ddbench import moe_scopes


def read(ctx):
    return moe_scopes.scope_ms(ctx, "moe_experts")
