"""Readers of what the ``nemotron_h_lm`` family adds to the program: device
time under the ``mamba_mixer`` scope (norm, ``W_in``, convolution, scan,
gated norm, ``W_out``) and under ``mamba_conv`` and ``ssd`` inside it,
forward, recomputed forward and backward, whatever implements them; the
scan's roofline share; and the roofline share of the ungated experts'
grouped products. The join of trace and compiled module is ``scopes.py``'s
(instruction name to ``op_name``); the expert layer's times and loads are
``moe_scopes.py``'s. Every reader gives ``None`` where the program has no
such scope (a parent commit, another family, a dry run)."""

from __future__ import annotations

import re

from ddbench import flops, moe_scopes, nemotron_flops, scopes

_INSTRUCTION = re.compile(r"^(%[^ ]+) = ")
NAMES = ("mamba_mixer", "mamba_conv", "ssd")


def _scope_seconds(ctx):
    """``{name: seconds summed over the chips}`` of the traced window for
    the operations with ``name`` anywhere in their scope path (an ``ssd``
    operation is a ``mamba_mixer`` one too), by ``scopes.innermost_ns``."""
    trace, job = ctx["trace"], ctx["job"]
    compiled = getattr(job, "_compiled", None)
    if trace is None or not ctx["traced_steps"] or compiled is None:
        return None
    if hasattr(trace, "nemotron_scope_seconds"):
        return trace.nemotron_scope_seconds
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
    trace.nemotron_scope_seconds = {k: v * 1e-9 for k, v in out.items()}
    return trace.nemotron_scope_seconds


def scope_ms(ctx, name: str):
    """Device ms a step, mean over the chips, under one of the scopes."""
    secs = _scope_seconds(ctx)
    if secs is None or not secs[name]:
        return None
    return secs[name] * 1e3 / (ctx["traced_steps"] * len(
        ctx["trace"].devices))


def _is_family(job) -> bool:
    return "hybrid_override_pattern" in getattr(job, "config", {})


def _least_seconds(ctx, work, moved):
    """The least time the chip could take for ``work`` FLOPs and ``moved``
    bytes: the larger of each over its peak."""
    peak = flops.peaks(ctx["device_kind"])
    return max(work / peak["bf16_flops_per_s"],
               moved / peak["hbm_bytes_per_s"])


def ssd_roofline(ctx):
    """Percent: the least time the chip could take for the state-space
    scans of the traced steps (``nemotron_flops.ssd_flops_bytes``: their
    bytes over the HBM peak or their FLOPs over the bf16 peak, the larger)
    over the time under ``ssd``."""
    secs, job = _scope_seconds(ctx), ctx["job"]
    if secs is None or not secs["ssd"] or not _is_family(job):
        return None
    least = _least_seconds(ctx, *nemotron_flops.ssd_flops_bytes(
        job.config, job.batch * job.seq))
    return 100.0 * least * ctx["traced_steps"] / secs["ssd"]


def experts_roofline(ctx):
    """Percent: as ``moe_scopes.experts_roofline``, with the work of
    ungated experts (two products a pair)."""
    secs, held = moe_scopes._scope_seconds(ctx), moe_scopes.held_loads(ctx)
    if secs is None or held is None or not secs["moe_experts"] \
            or not _is_family(ctx["job"]):
        return None
    least = _least_seconds(ctx, *nemotron_flops.expert_flops_bytes(
        ctx["job"].config, float(held.sum()), held.shape[0] * held.shape[1]))
    return 100.0 * least / secs["moe_experts"]
