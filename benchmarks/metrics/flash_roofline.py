"""Roofline share of the flash-attention kernels: the least time the chips
could take for the attention of the traced steps -- the larger of FLOPs over
the bf16 peak and bytes over the HBM peak, both from the call's shapes
(``ddbench/flops.py:flash_flops_bytes_per_step``) -- over the time the
kernels took, summed over the chips. At these shapes the FLOPs bound it."""

from ddbench import flops

from ddbench.spec import load_module


def read(ctx):
    trace, job = ctx["trace"], ctx["job"]
    if trace is None or not ctx["traced_steps"] \
            or getattr(job, "flash_flops", None) is None:
        return None
    pattern = load_module("metrics", "flash_time_share").FLASH_KERNELS
    seconds, events = trace.seconds_matching(pattern)
    if not events:
        return None
    peak = flops.peaks(ctx["device_kind"])
    least = max(job.flash_flops / peak["bf16_flops_per_s"],
                job.flash_bytes / peak["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_steps"] / seconds
