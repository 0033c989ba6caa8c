"""Device time a step, mean over the chips, of the operations under the
``optimizer`` scope (Adam and ``apply_updates``). A fusion XLA makes of a
weight gradient and its update counts where its ``op_name`` puts it."""

from ddbench import scopes


def read(ctx):
    return scopes.class_ms(ctx, "optimizer")
