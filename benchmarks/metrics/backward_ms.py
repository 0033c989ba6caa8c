"""Device time a step, mean over the chips, of the backward pass: the
transposed operations (``profile.describe``), the backward kernels whole
(what they recompute inside their bodies is theirs)."""

from ddbench import passes


def read(ctx):
    return passes.pass_ms(ctx, "backward")
