"""Device time a step, mean over the chips, of the forward pass: the
operations whose ``op_name`` is neither transposed, recomputed nor the
optimizer's (``profile.describe``)."""

from ddbench import passes


def read(ctx):
    return passes.pass_ms(ctx, "forward")
