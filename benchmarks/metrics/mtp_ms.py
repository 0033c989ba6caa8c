"""Device time a step, mean over the chips, of the operations under the
``mtp`` scope at any depth: the MTP module's embedding, ``eh_proj``, expert
block, norm and head pass, forward and transposed (less its grouped products,
which XLA leaves no scope)."""

from ddbench import moe_scopes


def read(ctx):
    return moe_scopes.scope_ms(ctx, "mtp")
