"""Device time a step, mean over the chips, of the operations under the
``moe_dispatch`` scope: router, top-k, the two sorts, the gather to the
experts' rows and the weighted way back, forward and transposed."""

from ddbench import moe_scopes


def read(ctx):
    return moe_scopes.scope_ms(ctx, "moe_dispatch")
