"""Device busy time in the traced window over the steps the loop made in it,
mean over the chips."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["traced_steps"]:
        return None
    return trace.busy_s() / ctx["traced_steps"] * 1e3
