"""Share of the window the loop spent inside ``bench:next_batch``, blocked on
the loader, on the host clock. It is the trainer's wait for data, not the
device's idle share (that is in ``device``)."""


def read(ctx):
    return ctx["span_s"].get("next_batch", 0.0) / ctx["window_s"]
