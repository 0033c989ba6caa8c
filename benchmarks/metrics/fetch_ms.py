"""Mean ``ddstore:fetch`` span begun in the traced window: plan, remote reads
and copy of one batch."""

from ddbench import scopes


def read(ctx):
    return scopes.span_mean_ms(ctx, "ddstore:fetch")
