"""Device time a step, mean over the chips, of what is computed a second
time: a rematerialised block's forward again inside the backward pass
(``rematted_computation``) and what a hand-written rule replays under the
program's ``recompute`` marker. ``counters()["remat"]`` says which blocks,
and what they save instead."""

from ddbench import passes


def read(ctx):
    return passes.pass_ms(ctx, "recompute")
