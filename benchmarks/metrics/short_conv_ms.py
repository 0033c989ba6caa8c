"""Device time a step, mean over the chips, of the operations under the
``short_conv`` scope: the two gates and the three taps between ``W_in`` and
``W_out``, forward, recomputed forward and transposed, whatever implements
them."""

from ddbench import lfm2_scopes


def read(ctx):
    return lfm2_scopes.scope_ms(ctx, "short_conv")
