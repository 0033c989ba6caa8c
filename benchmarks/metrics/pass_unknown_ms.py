"""Device time a step, mean over the chips, of the operations whose
``op_name`` says nothing of the program's pass: none at all (async waits,
layout copies, fusions XLA strips) or XLA's own (its ``ragged-dot-*``
kernels). With ``forward_ms``, ``recompute_ms``, ``backward_ms`` and the
optimizer's update it adds up to ``device_step_ms``."""

from ddbench import passes


def read(ctx):
    return passes.pass_ms(ctx, passes.UNKNOWN)
