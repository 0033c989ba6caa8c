"""Tokens fetched, staged and trained on per second and chip: every token of
every step the window completed, over the whole window (first dispatch to the
last step's completion), store in the path."""


def read(ctx):
    if ctx["unit"] != "tokens":
        return None
    return ctx["units"] / ctx["window_s"] / ctx["chips"]
