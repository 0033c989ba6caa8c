"""Device time a step, mean over the chips, of the shared expert's products
and activation (innermost scope ``shared_expert``), every pass."""

from ddbench import passes


def read(ctx):
    return passes.kind_ms(ctx, "shared_expert")
