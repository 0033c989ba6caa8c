"""Model FLOP/s utilisation over the traced window: the FLOPs the forward and
backward passes of its steps require (``ddbench/flops.py:lm_flops_per_step``;
recomputation does not count) over the window's length, chips and the bf16
peak of ``peaks.json``. Taken over the traced window and not the whole run,
because starting and stopping the profiler stalls a traced run's loop."""

from ddbench import flops


def read(ctx):
    trace = ctx["trace"]
    per_step = getattr(ctx["job"], "flops_per_step", None)
    if trace is None or per_step is None or not ctx["traced_steps"]:
        return None
    peak = flops.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_step * ctx["traced_steps"] / trace.window_s \
        / (ctx["chips"] * peak)
