"""Seconds JAX reported for tracing ``ddstore_lm_train_step`` and lowering it
to a module (``profile.counters()``): the part of ``compile_s`` a warm cache
does not save."""

from ddbench import scopes


def read(ctx):
    return scopes.trace_lower_s(ctx, "ddstore_lm_train_step")
