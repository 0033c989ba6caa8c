"""The names the program gives, read back: which class of work each device
operation of a traced window belongs to, the loader's ``ddstore:*`` spans
inside the window, and the set-up phases and compile counters of
``ddstore_tpu/utils/profile.py``.

On this libtpu a kernel's ``pallas_call(name=)`` becomes the name of its HLO
instruction, which is what the trace calls the operation
(``%ddstore_flash_fwd.8 = ... custom-call(...)``); a ``jax.named_scope`` is
only in the compiled module's ``metadata={op_name="jit(f)/.../attn/..."}``,
so the operations of the trace are joined to the module's text by instruction
name. Nothing here looks at an instruction's number, shape or layer.

Every reader over this file gives ``None`` where the program has no such
name, span, phase or counter (a commit before the names, a dry run).
"""

from __future__ import annotations

import heapq
import re

from ddbench import flops, tracered

FLASH_KERNELS = ("ddstore_flash_fwd", "ddstore_flash_dq", "ddstore_flash_dkv")
# Per live (query, key) pair and head, in units of head_dim FLOPs, and the
# arrays of head_dim and the float32 row vectors each kernel reads or writes
# once (``flops.flash_flops_bytes_per_step``: 18 d in all).
_KERNEL_WORK = {"ddstore_flash_fwd": (4, 4, 1),    # q k v -> o, lse
                "ddstore_flash_dq": (6, 5, 2),     # q k v do (lse delta) -> dq
                "ddstore_flash_dkv": (8, 6, 2)}    # q k v do -> dk dv
# A scope's class. ``attn`` and ``mlp`` are one class, the blocks' dense
# work; the innermost scope of an operation decides, so a ring step's
# combine and permutes are the ring's, not the block's around it.
SCOPES = {"attn": "block_dense", "mlp": "block_dense", "head": "head",
          "optimizer": "optimizer", "embed": "other_named",
          "ring_step": "other_named"}
UNNAMED = "unnamed"
CLASSES = FLASH_KERNELS + ("block_dense", "head", "optimizer", "other_named",
                           UNNAMED)

_NAMED = re.compile(r'^\s*(?:ROOT )?(%[^ ]+) = .*\bop_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^(%[^ ]+) = ")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def op_names(hlo_text: str) -> dict:
    """``{instruction: op_name}`` of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _NAMED.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _components(op_name: str) -> list:
    """``jit(f)/transpose(jvp(head))/while`` -> ``[f, head, while]``: a
    transformation wraps the scope it was applied under."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        out.append(part)
    return out


def classify(event_name: str, names: dict) -> str:
    """The class of one operation of the trace (its whole HLO instruction)."""
    m = _INSTRUCTION.match(event_name)
    if not m:
        return UNNAMED
    inst = m.group(1)
    parts = _components(names.get(inst, ""))
    # the instruction's own name, less its number: the kernel's, for a
    # Mosaic call
    parts.append(inst[1:].split(".")[0])
    for part in reversed(parts):
        if part in FLASH_KERNELS:
            if tracered.opcode(event_name) == "custom-call":
                return part
        elif part in SCOPES:
            return SCOPES[part]
    return UNNAMED


def innermost_ns(ops) -> list:
    """Nanoseconds of one chip's busy time that belong to each operation:
    every instant goes to the running operation that began last, so the
    shares add up to the busy time whatever the nesting. ``Op.own`` is the
    same where a body's operations lie strictly inside their loop; on four
    chips a child's end, rounded to a nanosecond, can pass its
    ``conditional``'s by one, and ``own`` then counts the child twice."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    points = sorted({o.start for o in ops} | {o.end for o in ops})
    share, running, nxt = [0] * len(ops), [], 0
    for lo, hi in zip(points, points[1:]):
        while nxt < len(order) and ops[order[nxt]].start <= lo:
            heapq.heappush(running, (-ops[order[nxt]].start, -nxt, order[nxt]))
            nxt += 1
        while running and ops[running[0][2]].end <= lo:
            heapq.heappop(running)
        if running:
            share[running[0][2]] += hi - lo
    return share


def partition(trace, hlo_text: str):
    """``({class: seconds}, {label: seconds})``: every instant of the chips'
    busy time in exactly one class, by the operation that owns it
    (``innermost_ns``), summed over the chips, so that the classes add up to
    the busy time; and the unnamed operations by ``tracered.label``.
    ``None`` where no kernel carries its name: that program is not
    described by these names."""
    names = op_names(hlo_text)
    classes = dict.fromkeys(CLASSES, 0)
    unnamed = {}
    for ops in trace.devices.values():
        for op, ns in zip(ops, innermost_ns(ops)):
            cls = classify(op.name, names)
            classes[cls] += ns
            if cls == UNNAMED and ns:
                key = tracered.label(op.name)
                unnamed[key] = unnamed.get(key, 0) + ns
    if not any(classes[k] for k in FLASH_KERNELS):
        return None
    return ({k: v * 1e-9 for k, v in classes.items()},
            {k: v * 1e-9 for k, v in unnamed.items()})


def _classes(trace, job, steps):
    compiled = getattr(job, "_compiled", None)
    if compiled is None:
        return None
    found = partition(trace, compiled.as_text())
    if found is None:
        return None
    classes, unnamed = found
    per = 1e3 / (steps * len(trace.devices))
    busy = trace.busy_s() * 1e3 / steps
    top = sorted(unnamed.items(), key=lambda kv: -kv[1])[:8]
    print("scopes: ms a step and chip: "
          + ", ".join(f"{k} {v * per:.3f}" for k, v in classes.items())
          + f"; sum {sum(classes.values()) * per:.3f}, busy {busy:.3f}; "
          + "largest unnamed: "
          + ", ".join(f"{k} {v * per:.3f}" for k, v in top), flush=True)
    return classes


def classes_of(ctx):
    """``{class: seconds summed over the chips}`` of the traced window. The
    readers of one run share one partition, kept on the trace; the first of
    them prints it whole, for ``PERF.md``."""
    trace = ctx["trace"]
    if trace is None or not ctx["traced_steps"]:
        return None
    if not hasattr(trace, "scope_classes"):
        trace.scope_classes = _classes(trace, ctx["job"],
                                       ctx["traced_steps"])
    return trace.scope_classes


def class_ms(ctx, cls: str):
    """Own time a step of the operations of one class, mean over the chips."""
    classes = classes_of(ctx)
    if classes is None or not classes[cls]:
        return None
    return classes[cls] * 1e3 / (ctx["traced_steps"] * len(
        ctx["trace"].devices))


def unnamed_share(ctx):
    classes = classes_of(ctx)
    if classes is None:
        return None
    return classes[UNNAMED] / sum(classes.values())


def flash_kernel_work(job) -> dict:
    """``{kernel: (FLOPs, bytes)}`` of one training step, from the shapes
    ``flops.flash_flops_bytes_per_step`` takes; the three add up to it."""
    import jax.numpy as jnp

    model = job.model
    d = model.dim // job.heads
    itemsize = jnp.dtype(model.compute_dtype).itemsize
    pairs = job.seq * (job.seq + 1) // 2 * job.batch * job.heads * model.layers
    rows = job.batch * job.heads * job.seq * model.layers
    return {k: (float(f * d * pairs), float(rows * (a * d * itemsize + 4 * v)))
            for k, (f, a, v) in _KERNEL_WORK.items()}


def flash_roofline(ctx, kernel: str):
    """Percent: the least time the chips could take for one kernel's share of
    the traced steps' attention (FLOPs over the bf16 peak or bytes over the
    HBM peak, the larger) over the time the kernel of that name took, summed
    over the chips. ``flash_roofline`` is the three together."""
    classes = classes_of(ctx)
    if classes is None or not classes[kernel]:
        return None
    work, moved = flash_kernel_work(ctx["job"])[kernel]
    peak = flops.peaks(ctx["device_kind"])
    least = max(work / peak["bf16_flops_per_s"],
                moved / peak["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_steps"] / classes[kernel]


def _spans(ctx, name):
    trace = ctx["trace"]
    if trace is None:
        return None
    return trace.host.get(name) or None


def span_share(ctx, name: str):
    """Share of the traced window covered by the host spans of one name,
    clipped to the window."""
    spans = _spans(ctx, name)
    if spans is None:
        return None
    lo, hi = ctx["trace"].window
    inside = sum(min(e, hi) - max(s, lo) for s, e in spans
                 if s < hi and e > lo)
    return inside / (hi - lo)


def span_mean_ms(ctx, name: str):
    """Mean length of the host spans of one name begun inside the window."""
    spans = _spans(ctx, name)
    if spans is None:
        return None
    lo, hi = ctx["trace"].window
    begun = [e - s for s, e in spans if lo <= s < hi]
    return sum(begun) / len(begun) * 1e-6 if begun else None


def _profile(ctx):
    """The program's ``utils/profile.py``; its phase log and counters say
    nothing of a dry run's CPU."""
    if ctx["cell"].dry_run:
        return None
    from ddstore_tpu.utils import profile

    return profile


def phase_s(ctx, name: str):
    """Seconds of this process's set-up phases of one name, summed."""
    phases = getattr(_profile(ctx), "phases", None)
    if phases is None:
        return None
    mine = [p["end_ns"] - p["start_ns"] for p in phases()
            if p["name"] == name]
    return sum(mine) * 1e-9 if mine else None


def trace_lower_s(ctx, fun_name: str):
    """Seconds JAX reported for tracing one jitted function and lowering it
    to a module, summed over every time it did."""
    counters = getattr(_profile(ctx), "counters", None)
    if counters is None:
        return None
    per_fun = counters()["compile_s"].get(fun_name)
    if not per_fun:
        return None
    return per_fun.get("trace_s", 0.0) + per_fun.get("lower_s", 0.0)
