"""Share of device busy time in operations that no kernel name and no scope
of the program claims (copies, async slices, layout changes): what the
program's names cannot see."""

from ddbench import scopes


def read(ctx):
    return scopes.unnamed_share(ctx)
