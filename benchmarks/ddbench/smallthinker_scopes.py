"""Readers of what the ``smallthinker_moe_lm`` family adds to the program:
the three flash kernels' device time under the ``window`` scope (the
sliding-window layers' attention), their roofline share from the window's
live pairs, and the blocks the window calls' grids visit over the blocks
that hold a live pair (``counters()["flash_geometry"]``). The join of trace
and compiled module is ``scopes.py``'s, the grammar of an ``op_name``
the program's (``profile.describe``); the expert layer's readers are
``moe_scopes.py``'s. Every reader gives ``None`` where the program has no
such scope, kernel or counter (a parent commit, another family, a dry
run)."""

from __future__ import annotations

import re

from ddbench import flops, passes, scopes

_INSTRUCTION = re.compile(r"^(%[^ ]+) = ")
WINDOW = "window"


def _window_flash_seconds(ctx):
    """Seconds of the ``ddstore_flash_*`` kernels whose ``op_name`` holds
    the ``window`` scope, summed over the chips and the traced steps (each
    instant to the operation that owns it, ``scopes.innermost_ns``)."""
    trace, job = ctx["trace"], ctx["job"]
    compiled = getattr(job, "_compiled", None)
    program = passes._program()
    if trace is None or not ctx["traced_steps"] or compiled is None \
            or program is None:
        return None
    if not hasattr(trace, "window_flash_s"):
        describe = program[0]
        names = scopes.op_names(compiled.as_text())
        total = 0
        for ops in trace.devices.values():
            for op, ns in zip(ops, scopes.innermost_ns(ops)):
                m = _INSTRUCTION.match(op.name)
                found = describe(names.get(m.group(1), "")) if m else ((),)
                kinds = found[0]
                if kinds and kinds[-1] in scopes.FLASH_KERNELS \
                        and WINDOW in kinds:
                    total += ns
        trace.window_flash_s = total * 1e-9 or None
    return trace.window_flash_s


def _is_family(job) -> bool:
    return "sliding_window_layout" in getattr(job, "config", {})


def flash_ms(ctx):
    """Device ms a step, mean over the chips, of the three flash kernels
    under ``window``."""
    secs = _window_flash_seconds(ctx)
    if secs is None or not _is_family(ctx["job"]):
        return None
    return secs * 1e3 / (ctx["traced_steps"] * len(ctx["trace"].devices))


def flash_roofline(ctx):
    """Percent: the least time the chip could take for the windowed layers'
    attention of the traced steps (``job.window_flops`` /
    ``job.window_bytes``, ``smallthinker_flops.flash_flops_bytes_per_step``
    over those layers: FLOPs over the bf16 peak or bytes over the HBM peak,
    the larger) over the three kernels' time under ``window``."""
    secs, job = _window_flash_seconds(ctx), ctx["job"]
    if secs is None or not _is_family(job):
        return None
    peak = flops.peaks(ctx["device_kind"])
    least = max(job.window_flops / peak["bf16_flops_per_s"],
                job.window_bytes / peak["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_steps"] / secs


def visited_over_live(ctx):
    """Blocks the three kernels' grids visit over blocks that hold a live
    pair, of every flash call traced under a sliding window: 1.0 where no
    block wholly outside the window is a step."""
    geometry = passes.counter(ctx, "flash_geometry")
    if not geometry:
        return None
    calls = [counts for kernel in scopes.FLASH_KERNELS
             for call, counts in geometry.get(kernel, {}).items()
             if call.startswith(WINDOW)]
    live = sum(c["blocks_live"] for c in calls)
    return sum(c["grid_steps"] for c in calls) / live if live else None
