"""Mean ``ddstore:stage`` span begun in the traced window: the enqueue of one
batch's host-to-device transfers (not their end)."""

from ddbench import scopes


def read(ctx):
    return scopes.span_mean_ms(ctx, "ddstore:stage")
