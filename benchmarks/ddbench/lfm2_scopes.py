"""Readers of what the ``lfm2_moe_lm`` family adds to the program: device
time under the ``conv_mixer`` scope (norm, ``W_in``, the gated short
convolution, ``W_out``) and under ``short_conv`` inside it (gates and taps
alone), forward, recomputed forward and backward, whatever implements them;
and the convolution's roofline share. The join of trace and compiled module
is ``scopes.py``'s (instruction name to ``op_name``); the expert layer's
readers are ``moe_scopes.py``'s. Every reader gives ``None`` where the
program has no such scope (a parent commit, another family, a dry run)."""

from __future__ import annotations

import re

from ddbench import flops, lfm2_flops, scopes

_INSTRUCTION = re.compile(r"^(%[^ ]+) = ")
NAMES = ("conv_mixer", "short_conv")


def _scope_seconds(ctx):
    """``{name: seconds summed over the chips}`` of the traced window for
    the operations with ``name`` anywhere in their scope path (a
    ``short_conv`` operation is a ``conv_mixer`` one too), by
    ``scopes.innermost_ns``."""
    trace, job = ctx["trace"], ctx["job"]
    compiled = getattr(job, "_compiled", None)
    if trace is None or not ctx["traced_steps"] or compiled is None:
        return None
    if hasattr(trace, "lfm2_scope_seconds"):
        return trace.lfm2_scope_seconds
    names = scopes.op_names(compiled.as_text())
    out = dict.fromkeys(NAMES, 0)
    for ops in trace.devices.values():
        for op, ns in zip(ops, scopes.innermost_ns(ops)):
            m = _INSTRUCTION.match(op.name)
            if not m or not ns:
                continue
            parts = scopes._components(names.get(m.group(1), ""))
            for name in NAMES:
                if name in parts:
                    out[name] += ns
    trace.lfm2_scope_seconds = {k: v * 1e-9 for k, v in out.items()}
    return trace.lfm2_scope_seconds


def scope_ms(ctx, name: str):
    """Device ms a step, mean over the chips, under one of the scopes."""
    secs = _scope_seconds(ctx)
    if secs is None or not secs[name]:
        return None
    return secs[name] * 1e3 / (ctx["traced_steps"] * len(
        ctx["trace"].devices))


def short_conv_roofline(ctx):
    """Percent: the least time the chip could take for the gated short
    convolutions of the traced steps (their bytes,
    ``lfm2_flops.short_conv_bytes``, over the HBM peak: 3 L multiply-adds
    a channel never bound it) over the time under ``short_conv``."""
    secs = _scope_seconds(ctx)
    job = ctx["job"]
    if secs is None or not secs["short_conv"]:
        return None
    moved = lfm2_flops.short_conv_bytes(
        job.config, job.batch * job.seq) * ctx["traced_steps"]
    peak = flops.peaks(ctx["device_kind"])
    return 100.0 * moved / peak["hbm_bytes_per_s"] / secs["short_conv"]
