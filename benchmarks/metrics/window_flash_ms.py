"""Device time a step, mean over the chips, of the three flash kernels
(``ddstore_flash_fwd``, ``_dq``, ``_dkv``, by name) under the ``window``
scope: the sliding-window layers' attention."""

from ddbench import smallthinker_scopes


def read(ctx):
    return smallthinker_scopes.flash_ms(ctx)
