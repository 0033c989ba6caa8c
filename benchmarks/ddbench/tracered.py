"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the metric
readers use: per-chip busy time, time per device operation, idle gaps with
the host span that covered each, kernel time by name pattern, and collective
time not hidden behind compute.

Read with nothing but JAX (``jax.profiler.ProfileData``). All times are in
nanoseconds on the profiler's clock until the public accessors, which give
seconds. ``tests/test_tracered.py`` checks this file against a small trace
recorded on the v5e and kept beside it.
"""

from __future__ import annotations

import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench:traced_window"
HOST_SPANS = re.compile(r"^(bench:|ddstore:)")
# On the TPU an operation's name is its whole HLO instruction,
# ``%name.7 = type opcode(operands), attributes``.
_INSTRUCTION = re.compile(r"^(%[^ ]+) = .*? ([a-z][a-z0-9\-]*)\(")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast", "send", "recv")


def opcode(name: str) -> str:
    m = _INSTRUCTION.match(name)
    return m.group(2) if m else name


def is_collective(name: str) -> bool:
    op = opcode(name)
    for suffix in ("-start", "-done"):
        if op.endswith(suffix):
            op = op[:-len(suffix)]
    return op in COLLECTIVES


def label(name: str) -> str:
    """A name short enough to print: instruction and opcode, and the target
    of a custom call. A number inside the instruction's name (one per layer:
    ``%block5.4``) becomes ``*``, so the layers' copies of one operation are
    summed."""
    m = _INSTRUCTION.match(name)
    if not m:
        return name[:120]
    inst = re.sub(r"\d+(?=\.\d+$)", "*", m.group(1))
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{inst} {m.group(2)}" + (f" {target.group(1)}" if target else "")


def _union(intervals):
    """Sorted, merged copy of [(start, end), ...]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _measure(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


class Op(NamedTuple):
    """One device operation, clipped to the window. ``own`` is its span less
    the operations inside it; ``leaf`` says nothing is inside it."""

    name: str
    start: int
    end: int
    own: int
    leaf: bool


def _nest(ops):
    """One chip's ``(name, start, end)`` as ``Op``s: a ``while`` or
    ``conditional`` holds the operations of its body, so its own time is its
    span less theirs."""
    out, stack = [], []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        # inside the operation on top of the stack, or not its child
        while stack and out[stack[-1]][2] < e:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= e - s
            parent[4] = False
        out.append([name, s, e, e - s, True])
        stack.append(len(out) - 1)
    return [Op(*o) for o in out]


class Reduced:
    """One traced window. ``devices`` maps chip ordinal to its ``Op``s,
    clipped to the window; ``host`` maps a span name to
    ``[(start, end), ...]``."""

    def __init__(self, window, devices, host):
        self.window = window
        self.devices = {d: _nest(ops) for d, ops in devices.items()}
        self.host = host

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s_per_device(self) -> dict:
        return {d: _measure(_union([(o.start, o.end) for o in ops])) * 1e-9
                for d, ops in self.devices.items()}

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per = self.busy_s_per_device()
        return sum(per.values()) / len(per) if per else 0.0

    def op_seconds(self) -> dict:
        """Seconds of own time per operation (by ``label``), averaged over
        the chips. Own time: a loop's span less the operations inside it."""
        tot = {}
        for ops in self.devices.values():
            for op in ops:
                key = label(op.name)
                tot[key] = tot.get(key, 0) + op.own
        n = max(1, len(self.devices))
        return {k: v * 1e-9 / n for k, v in tot.items()}

    def seconds_matching(self, pattern: str):
        """(seconds summed over the chips, events) of the operations whose
        name matches ``pattern``."""
        rx = re.compile(pattern)
        ns = count = 0
        for ops in self.devices.values():
            for op in ops:
                if rx.search(op.name):
                    ns += op.end - op.start
                    count += 1
        return ns * 1e-9, count

    def collective_exposed_s(self) -> float:
        """Seconds of collective operations during which no other operation
        ran on that chip, averaged over the chips. A loop or conditional that
        merely holds a collective is not other work: only operations with
        nothing inside them count."""
        per = self.collective_exposed_s_per_device()
        return sum(per.values()) / len(per) if per else 0.0

    def collective_exposed_s_per_device(self) -> dict:
        per = {}
        for d, ops in self.devices.items():
            leaves = [o for o in ops if o.leaf]
            coll = _union([(o.start, o.end) for o in leaves
                           if is_collective(o.name)])
            comp = _union([(o.start, o.end) for o in leaves
                           if not is_collective(o.name)])
            per[d] = _measure(_subtract(coll, comp)) * 1e-9
        return per

    def idle_gaps(self, top: int = 5):
        """The longest gaps in which no operation ran on a chip, each with
        the host span that overlapped it most: ``[(label, seconds), ...]``."""
        gaps = []
        for d, ops in self.devices.items():
            busy = _union([(o.start, o.end) for o in ops])
            for s, e in _subtract([list(self.window)], busy):
                gaps.append((e - s, s, e, d))
        gaps.sort(reverse=True)
        out = []
        for dur, s, e, d in gaps[:top]:
            best, best_ns = "no bench or ddstore span", 0
            for name, spans in self.host.items():
                if name == WINDOW_SPAN:
                    continue
                ns = sum(min(e, b) - max(s, a) for a, b in spans
                         if a < e and b > s)
                if ns > best_ns:
                    best, best_ns = name, ns
            out.append((f"chip {d}: {best}", dur * 1e-9))
        return out

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps(5)]}


def reduce_profile(profile) -> Reduced:
    """``profile`` is a ``jax.profiler.ProfileData``. The window is the host
    span ``bench:traced_window``; a trace without it, or without a device
    plane, has nothing to read and gives ``None``."""
    host, devices = {}, {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPANS.match(ev.name):
                        host.setdefault(ev.name, []).append(
                            (int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns)))
    if WINDOW_SPAN not in host or not devices:
        return None
    lo, hi = host[WINDOW_SPAN][0]
    clipped = {d: [(name, max(s, lo), min(e, hi)) for name, s, e in ops
                   if min(e, hi) > max(s, lo)]
               for d, ops in devices.items()}
    return Reduced((lo, hi), clipped, host)


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
