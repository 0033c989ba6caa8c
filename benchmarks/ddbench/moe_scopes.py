"""Readers of what the ``mla_moe_lm`` family adds to the program: device
time under the ``moe_dispatch``, ``moe_experts`` and ``mtp`` scopes, the
grouped products' roofline share, and the load vectors the step returns.

The join of trace and compiled module is ``scopes.py``'s (instruction name
to ``op_name``). One addition: XLA's own lowering of ``lax.ragged_dot`` on
the TPU replaces the ``op_name`` of the kernels it emits by its own
(``ragged-dot-none``, ``ragged-dot-metadata``), so the grouped products are
found by that name as well as under the ``moe_experts`` scope, whatever
implements them. Every reader gives ``None`` where the program has no such
scope or counter (a parent commit, another family, a dry run)."""

from __future__ import annotations

import re

from ddbench import flops, moe_flops, scopes

_RAGGED = "ragged-dot"
_INSTRUCTION = re.compile(r"^%([^ ]+) = ")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
# Window seconds before benchmarks/run.py starts the profiler
# (TRACE_AFTER_S there) and the iterations it then lets pass.
_TRACE_AFTER_S, _TRACE_SETTLE = 2.0, 3


def _path(op_name: str) -> list:
    """``jit(f)/transpose(jvp(mlp))/moe_experts`` -> ``[f, mlp,
    moe_experts]``: a transformation wraps the scope it was applied under."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        out.append(part)
    return out


def _scope_seconds(ctx):
    """``{name: seconds summed over the chips}`` of the traced window for
    ``moe_dispatch`` and ``moe_experts`` (innermost of the two decides) and
    ``mtp`` (anywhere in the path; the MTP block's ragged-dot kernels,
    which carry no path, are the experts' alone), by
    ``scopes.innermost_ns``."""
    trace, job = ctx["trace"], ctx["job"]
    compiled = getattr(job, "_compiled", None)
    if trace is None or not ctx["traced_steps"] or compiled is None:
        return None
    if hasattr(trace, "moe_scope_seconds"):
        return trace.moe_scope_seconds
    names = scopes.op_names(compiled.as_text())
    out = dict.fromkeys(("moe_dispatch", "moe_experts", "mtp"), 0)
    for ops in trace.devices.values():
        for op, ns in zip(ops, scopes.innermost_ns(ops)):
            m = _INSTRUCTION.match(op.name)
            if not m or not ns:
                continue
            inst = m.group(1)
            parts = _path(names.get("%" + inst, ""))
            if inst.startswith(_RAGGED):
                out["moe_experts"] += ns
                continue
            if "mtp" in parts:
                out["mtp"] += ns
            for part in reversed(parts):
                if part in ("moe_dispatch", "moe_experts"):
                    out[part] += ns
                    break
    trace.moe_scope_seconds = {k: v * 1e-9 for k, v in out.items()}
    return trace.moe_scope_seconds


def scope_ms(ctx, name: str):
    """Device ms a step, mean over the chips, under one of the scopes."""
    secs = _scope_seconds(ctx)
    if secs is None or not secs[name]:
        return None
    return secs[name] * 1e3 / (ctx["traced_steps"] * len(
        ctx["trace"].devices))


def _traced_loads(ctx):
    """(steps, expert layers, experts) int array: the load vectors of the
    steps the window traced, by their place in the run (the job keeps every
    step's; the profiler starts ``_TRACE_AFTER_S`` into the window and the
    traced window opens ``_TRACE_SETTLE`` iterations later), to within the
    benchmark's dispatch lag, which routing does not notice."""
    import numpy as np

    loads = getattr(ctx["job"], "loads", None)
    n = ctx["traced_steps"]
    if not loads or not n or len(loads) < ctx["steps"]:
        return None
    window = list(loads)[-ctx["steps"]:]
    first = int(_TRACE_AFTER_S * ctx["steps"] / ctx["window_s"]) \
        + _TRACE_SETTLE
    first = max(0, min(first, len(window) - n))
    return np.stack([np.asarray(x) for x in window[first:first + n]])


def held_loads(ctx):
    """The traced steps' loads of the experts held here."""
    loads = _traced_loads(ctx)
    if loads is None:
        return None
    cfg = ctx["job"].config
    held = int(cfg["n_routed_experts"])
    first = int(cfg["expert_parallel"]["chip"]) * held
    return loads[:, :, first:first + held]


def experts_roofline(ctx):
    """Percent: the least time the chip could take for the grouped products
    of the traced steps (FLOPs of the routed pairs over the bf16 peak, or
    their bytes over the HBM peak, the larger) over the time under
    ``moe_experts``, XLA's ragged-dot kernels included."""
    secs, held = _scope_seconds(ctx), held_loads(ctx)
    if secs is None or held is None or not secs["moe_experts"]:
        return None
    work, moved = moe_flops.expert_flops_bytes(
        ctx["job"].config, float(held.sum()), held.shape[0] * held.shape[1])
    peak = flops.peaks(ctx["device_kind"])
    least = max(work / peak["bf16_flops_per_s"],
                moved / peak["hbm_bytes_per_s"])
    return 100.0 * least / secs["moe_experts"]


def load_max_over_mean(ctx):
    """Largest over mean load of the held experts, mean over the traced
    steps and the expert layers."""
    held = held_loads(ctx)
    if held is None:
        return None
    mean = held.mean(-1)
    return float((held.max(-1) / mean.clip(min=1e-9)).mean())
