"""One partition of a traced window's busy time by kind of work and by pass:
forward, recomputed forward (``nn.remat``'s second forward, and what a
hand-written rule computes again inside its backward), backward, the
optimizer's update.

The grammar of an ``op_name`` is the program's
(``ddstore_tpu.utils.profile.describe``, with the names in ``STEP_SCOPES``);
this file only joins: every instant of busy time to one operation
(``scopes.innermost_ns``), an operation of the trace to its ``op_name`` by
instruction name in the compiled module (``scopes.op_names``), and that to
the innermost scope and the pass ``describe`` reads. An operation whose
``op_name`` says nothing of the program (none at all: async waits, layout
copies, fusions XLA strips; or XLA's own: its ``ragged-dot-*`` kernels) is
in the column ``unknown``, and the table lists the largest by
``tracered.label``, so the hole is sized. Rows and columns add up to the
busy time.

``counter`` is the way to what the program counted beside its names
(``counters()["memory"]``, ``["compile_cache"]``).

Every reader gives ``None`` where the program has no such grammar (a
parent commit), no compiled module is kept, or nothing was traced (a dry
run); where the partition exists, an empty row or column reads 0.0.
"""

from __future__ import annotations

import re
import time

from ddbench import scopes, tracered

UNKNOWN = "unknown"
# Rows of operations that carry no known scope: with a pass (a jit's own
# operations between the scopes), and without one.
NO_SCOPE, NO_NAME = "(no scope)", "(no op_name)"
_INSTRUCTION = re.compile(r"^(%[^ ]+) = ")


def _program():
    """``(describe, PASSES)`` of the program, or ``None`` where it has no
    parser of its names (a commit before it)."""
    from ddstore_tpu.utils import profile

    describe = getattr(profile, "describe", None)
    return None if describe is None else (describe, tuple(profile.PASSES))


def partition(trace, hlo_text: str, describe):
    """``({(kind, pass): seconds}, {label: seconds})``: every instant of the
    chips' busy time under one kind of work (the innermost scope
    ``describe`` knows, a kernel's name included) and one pass (or
    ``UNKNOWN``), summed over the chips; and the operations of the column
    ``UNKNOWN`` by ``tracered.label``."""
    names = scopes.op_names(hlo_text)
    table, unknown, seen = {}, {}, {}
    for ops in trace.devices.values():
        for op, ns in zip(ops, scopes.innermost_ns(ops)):
            if not ns:
                continue
            m = _INSTRUCTION.match(op.name)
            op_name = names.get(m.group(1), "") if m else ""
            if op_name not in seen:
                kinds, which = describe(op_name)
                seen[op_name] = (
                    kinds[-1] if kinds else
                    NO_NAME if which is None else NO_SCOPE,
                    which or UNKNOWN)
            key = seen[op_name]
            table[key] = table.get(key, 0) + ns
            if key[1] == UNKNOWN:
                label = tracered.label(op.name)
                unknown[label] = unknown.get(label, 0) + ns
    return ({k: v * 1e-9 for k, v in table.items()},
            {k: v * 1e-9 for k, v in unknown.items()})


def _show(table, unknown, columns, per, busy_ms, took_s):
    """The table, once a run, for ``PERF.md``: ms a step and chip."""
    rows = sorted({k for k, _ in table},
                  key=lambda k: -sum(table.get((k, c), 0) for c in columns))
    width = max(len(r) for r in rows + ["kind of work"])
    lines = ["passes: ms a step and chip, by kind of work (innermost scope) "
             "and pass",
             f"  {'kind of work':<{width}}" + "".join(f"{c:>11}"
                                                      for c in columns)
             + f"{'all':>11}"]
    for r in rows:
        cells = [table.get((r, c), 0) * per for c in columns]
        lines.append(f"  {r:<{width}}" + "".join(f"{v:11.3f}" for v in cells)
                     + f"{sum(cells):11.3f}")
    sums = [sum(v for (_, c), v in table.items() if c == col) * per
            for col in columns]
    lines.append(f"  {'all':<{width}}" + "".join(f"{v:11.3f}" for v in sums)
                 + f"{sum(sums):11.3f}")
    top = sorted(unknown.items(), key=lambda kv: -kv[1])[:8]
    lines.append(f"  busy {busy_ms:.3f}; partitioned in {took_s:.2f} s; "
                 f"largest {UNKNOWN}: "
                 + ", ".join(f"{k} {v * per:.3f}" for k, v in top))
    print("\n".join(lines), flush=True)


def table_of(ctx):
    """``({(kind, pass): seconds summed over the chips}, columns)`` of the
    traced window. The readers of one run share one partition, kept on the
    trace; the first of them prints it whole."""
    trace = ctx["trace"]
    if trace is None or not ctx["traced_steps"] or not trace.devices:
        return None
    if not hasattr(trace, "pass_table"):
        trace.pass_table = None
        compiled = getattr(ctx["job"], "_compiled", None)
        program = _program()
        if compiled is not None and program is not None:
            describe, passes = program
            columns = passes + (UNKNOWN,)
            t0 = time.perf_counter()
            table, unknown = partition(trace, compiled.as_text(), describe)
            steps = ctx["traced_steps"]
            _show(table, unknown, columns,
                  1e3 / (steps * len(trace.devices)),
                  trace.busy_s() * 1e3 / steps, time.perf_counter() - t0)
            trace.pass_table = (table, columns)
    return trace.pass_table


def _ms(ctx, keep):
    found = table_of(ctx)
    if found is None:
        return None
    return sum(v for key, v in found[0].items() if keep(*key)) * 1e3 / (
        ctx["traced_steps"] * len(ctx["trace"].devices))


def pass_ms(ctx, which: str):
    """Device ms a step, mean over the chips, of one pass (or ``UNKNOWN``)."""
    return _ms(ctx, lambda kind, col: col == which)


def kind_ms(ctx, *kinds: str):
    """Device ms a step, mean over the chips, of the operations whose
    innermost scope is one of ``kinds``, every pass."""
    return _ms(ctx, lambda kind, col: kind in kinds)


def counter(ctx, name: str):
    """``profile.counters()[name]``, or ``None`` where the program counts no
    such thing (a parent commit; a dry run's CPU says nothing of a chip)."""
    profile = scopes._profile(ctx)
    counters = getattr(profile, "counters", None)
    return None if counters is None else counters().get(name)
