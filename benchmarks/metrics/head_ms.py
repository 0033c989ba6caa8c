"""Device time a step, mean over the chips, of the operations under the
``head`` scope: final norm, LM head and the (fused) cross-entropy, forward
and transposed."""

from ddbench import scopes


def read(ctx):
    return scopes.class_ms(ctx, "head")
