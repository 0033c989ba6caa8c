"""Share of the traced window in which a chip ran a collective operation and
nothing else, mean over the chips: communication not hidden behind compute."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return trace.collective_exposed_s() / trace.window_s
