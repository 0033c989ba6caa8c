"""Device time a step, mean over the chips, of the operations under the
blocks' ``attn`` and ``mlp`` scopes, forward and transposed, that are not a
flash kernel: layer norms, qkv, proj, up, GELU, down and their gradients."""

from ddbench import scopes


def read(ctx):
    return scopes.class_ms(ctx, "block_dense")
