"""Share of the traced window the consumer spent inside the loader's own
``ddstore:wait_batch`` span, blocked on the next batch: the program's measure
of what ``data_wait_share`` times from outside."""

from ddbench import scopes


def read(ctx):
    return scopes.span_share(ctx, "ddstore:wait_batch")
