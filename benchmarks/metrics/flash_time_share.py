"""Share of device busy time spent in the three flash-attention kernels
(forward, dq, dkv), summed over the chips.

The kernels carry no ``name=`` yet, and this libtpu's trace names an operation
by its HLO instruction, not by the kernel function (``_flash_kernel``,
``_bwd_dq_kernel``, ``_bwd_dkv_kernel`` appear nowhere in it). What the trace
does show is the Mosaic custom call, and in these step programs every Mosaic
custom call is one of the three flash kernels (three to a layer and ring step
in the lowered step, PERF.md section 6, PR 21). Stable names are the
``tracing`` issue's, and this pattern is then the one place to change."""

FLASH_KERNELS = r'custom-call\(.*custom_call_target="tpu_custom_call"'


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds, events = trace.seconds_matching(FLASH_KERNELS)
    busy = sum(trace.busy_s_per_device().values())
    return seconds / busy if events and busy else None
