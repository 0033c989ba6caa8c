"""Readers of what the ``sdar_moe_lm`` family adds to the program: the
three flash kernels' device time under the block-diffusion mask and their
roofline share from the mask's live pairs, the blocks their grids visit over
the blocks that hold a live pair (``counters()["flash_geometry"]``) and
device time under ``diffusion_noise``. The join of trace and compiled module
is ``scopes.py``'s; the expert layer's readers are ``moe_scopes.py``'s.
Every reader gives ``None`` where the
program has no such kernel, scope or counter (a parent commit, another
family, a dry run)."""

from __future__ import annotations

from ddbench import flops, passes, scopes


def _flash_seconds(ctx):
    """Seconds of the three ``ddstore_flash_*`` kernels, by name, summed
    over the chips and the traced steps."""
    classes = scopes.classes_of(ctx)
    if classes is None:
        return None
    return sum(classes[k] for k in scopes.FLASH_KERNELS) or None


def _is_family(job) -> bool:
    return bool(getattr(job, "config", {}).get("block_length"))


def flash_ms(ctx):
    """Device ms a step, mean over the chips, of the three flash kernels."""
    secs = _flash_seconds(ctx)
    if secs is None or not _is_family(ctx["job"]):
        return None
    return secs * 1e3 / (ctx["traced_steps"] * len(ctx["trace"].devices))


def flash_roofline(ctx):
    """Percent: the least time the chip could take for the attention of the
    traced steps under the mask (``sdar_flops.flash_flops_bytes_per_step``,
    which the family keeps as ``job.flash_flops`` / ``job.flash_bytes``:
    FLOPs over the bf16 peak or bytes over the HBM peak, the larger) over
    the three kernels' time."""
    secs, job = _flash_seconds(ctx), ctx["job"]
    if secs is None or not _is_family(job):
        return None
    peak = flops.peaks(ctx["device_kind"])
    least = max(job.flash_flops / peak["bf16_flops_per_s"],
                job.flash_bytes / peak["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_steps"] / secs


def visited_over_live(ctx):
    """Blocks the three kernels' grids visit over blocks that hold a live
    pair, of every flash call traced under a block-diffusion mask: 1.0
    where no dead block is a step."""
    geometry = passes.counter(ctx, "flash_geometry")
    if not geometry:
        return None
    calls = [counts for kernel in scopes.FLASH_KERNELS
             for call, counts in geometry.get(kernel, {}).items()
             if call.startswith("blockdiff")]
    live = sum(c["blocks_live"] for c in calls)
    return sum(c["grid_steps"] for c in calls) / live if live else None


def diffusion_noise_ms(ctx):
    """Device ms a step, mean over the chips, under ``diffusion_noise``:
    the draw, the noised window beside the clean one and the weights."""
    return passes.kind_ms(ctx, "diffusion_noise") or None
