"""Trace + lower + compile (or cache read) of the cell's step: the first call
of the step, completed, less the second."""


def read(ctx):
    return ctx["compile_s"]
