"""The ``ddstore:rendezvous`` phase of rank 0: ``FileGroup.__init__`` until
every rank is present."""

from ddbench import scopes


def read(ctx):
    return scopes.phase_s(ctx, "ddstore:rendezvous")
