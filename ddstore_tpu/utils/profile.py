"""The one tracing mechanism: names and spans on the JAX profiler's clock.

Everything the program says about where its time goes is read from one
place, a profiler trace (``.xplane.pb``), where host spans and device
operations share a timeline. No second recorder, no exporter, no switch:
the profiler being on is the switch, and an annotation outside a trace is
inert. The vocabulary (``ddstore`` prefix, or a scope of the step
``ddstore_lm_train_step``):

``ddstore_flash_fwd``, ``ddstore_flash_dq``, ``ddstore_flash_dkv``
    ``ops/attention.py``, ``pallas_call(name=)``. Device: the three flash
    kernels, named alike on one chip and in the ring's steps. On the
    v5e the name is the HLO instruction's, which is what a trace calls the
    operation (``%ddstore_flash_fwd.8 = ... custom-call(...)``).
``embed``, ``attn``, ``mlp``, ``head``, ``optimizer``; ``ring_step``
    ``models/transformer.py``; ``parallel/ring_attention.py``,
    ``jax.named_scope``. Device: in every operation's ``op_name``, forward
    and transposed (the compiled module's metadata; a trace does not
    repeat it, so a reader joins trace and module by instruction name).
``ddstore_lm_train_step``
    ``make_train_step``: the jitted step (``jit_ddstore_lm_train_step`` on
    a trace's ``XLA Modules`` line, ``fun_name`` in :func:`counters`).
``ddstore:wait_batch``
    ``data/loader.py``. Host: the consumer blocked on the next batch.
``ddstore:fetch``
    ``data/loader.py``. Host: plan, remote reads and copy of one batch.
``ddstore:stage``
    ``data/loader.py``. Host: the ENQUEUE of one batch's host-to-device
    transfers, not their end (the runtime's own
    ``TransferToDevice=>IssueEvent=>Done`` events are the end).
``ddstore:rendezvous``, ``ddstore:register``, ``ddstore:state_init``
    ``rendezvous.py``, ``store.py``, ``models/transformer.py``. Set-up
    phases: ``FileGroup.__init__`` until every rank is present; one
    collective ``DDStore.add`` (``rows``, ``bytes``); ``create_train_state``.

The loader's three spans of one batch share ``batch`` (its number in the
epoch); ``fetch`` carries ``rows``, ``stage`` ``rows`` and ``bytes``
(annotation arguments: event stats in the trace).

Set-up happens before anybody starts a profiler, so :func:`phase` also
appends to a small process-wide list that :func:`phases` reads back in
epoch nanoseconds; ``profile_start_time`` of the trace's ``Task
Environment`` plane plus an event's ``start_ns`` is the same clock (within
40 us on the v5e host, PERF.md), so a phase, or a ddtrace event (same
``CLOCK_MONOTONIC``), can be laid beside a trace. The log keeps the newest
1024 phases (a process makes a few, and one a ``DDStore.add``).
:func:`counters` holds the seconds ``jax.monitoring`` reports for tracing
each jitted function and lowering it to a module: the part of a start that a
warm compile cache does not take away (``utils.enable_compile_cache``
registers the listener; the benchmark's ``step_trace_lower_s`` reads the
step's).

To look at a training run: wrap a few steady steps in :func:`trace` (or
``python3 benchmarks/run.py --workload W --trace 1 --keep-trace FILE`` on
the chip), open the directory with xprof / TensorBoard, or read the
``.xplane.pb`` with ``jax.profiler.ProfileData``: device operations are on
the ``XLA Ops`` line of each ``/device:TPU:n`` plane, the spans on the host
planes' thread lines. ``benchmarks/ddbench/tracered.py`` and ``scopes.py``
are the reductions the benchmark's metrics use (``benchmarks/tests`` checks
them against recorded v5e slices).
"""

from __future__ import annotations

import collections
import contextlib
import sys
import threading
import time
from typing import Deque, Dict, Iterator, List

__all__ = ["trace", "annotate", "step_annotate", "phase", "phases",
           "watch_compiles", "count_geometry", "count_moe_layout",
           "count_mixer_layout", "count_ring_geometry", "counters"]

# One reading of both clocks, taken together: perf_counter_ns (what a phase
# records; CLOCK_MONOTONIC, as ddtrace) and the epoch clock a trace is
# anchored to.
_CLOCK_PAIR = (time.perf_counter_ns(), time.time_ns())

_lock = threading.Lock()
_phases: Deque[dict] = collections.deque(maxlen=1024)

# jax.monitoring names -> the short keys of counters().
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}
_compile_s: Dict[str, Dict[str, float]] = {}
_watching = False
# Kernel name -> call shape -> what ops/attention.py's geometry counts.
_geometry: Dict[str, Dict[str, Dict[str, int]]] = {}
# Expert layer (its module path) -> what models/moe.py holds and routes.
_moe_layout: Dict[str, dict] = {}
# Described layer (its module path) -> what its mixer is and works on.
_mixer_layout: Dict[str, dict] = {}
_ring_geometry: Dict[str, dict] = {}


@contextlib.contextmanager
def trace(logdir: str, *, create_perfetto_link: bool = False
          ) -> Iterator[None]:
    """Capture a JAX profiler trace of the enclosed block into
    ``logdir`` (TensorBoard ``plugins/profile`` layout)."""
    import jax

    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **kwargs):
    """Named host-side span on the profiler timeline (zero-cost when no
    trace is active). Keyword arguments become the event's stats. Usable
    as context manager or decorator."""
    import jax

    return jax.profiler.TraceAnnotation(name, **kwargs)


def step_annotate(step: int, name: str = "train_step"):
    """Step-scoped annotation: groups device ops under one training step
    in the trace viewer's step-time analysis."""
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=step)


@contextlib.contextmanager
def phase(name: str, **counts) -> Iterator[None]:
    """A set-up phase: an :func:`annotate` span (when JAX is loaded; a
    data-only process never imports it for this) and one entry of the
    process-wide phase log, closed even when the block raises. Usable as
    context manager or decorator, from any thread; phases may nest (the
    log says so by their times alone)."""
    entry = {"name": name, "start": time.perf_counter_ns(), "end": None,
             "counts": dict(counts)}
    with _lock:
        _phases.append(entry)
    span = annotate(name, **counts) if "jax" in sys.modules \
        else contextlib.nullcontext()
    try:
        with span:
            yield
    finally:
        entry["end"] = time.perf_counter_ns()


def phases() -> List[dict]:
    """The closed phases of this process (the newest 1024), in the order
    they began: ``name``, ``start_ns`` and ``end_ns`` in epoch nanoseconds
    (a trace's ``profile_start_time`` + an event's ``start_ns`` is the same
    clock) and ``counts``."""
    mono0, epoch0 = _CLOCK_PAIR
    with _lock:
        snap = list(_phases)
    return [{"name": e["name"],
             "start_ns": epoch0 + e["start"] - mono0,
             "end_ns": epoch0 + e["end"] - mono0,
             "counts": dict(e["counts"])}
            for e in snap if e["end"] is not None]


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    """Sums trace and lowering seconds per ``fun_name``. JAX reports
    tracing under ``f`` and lowering under ``jit(f)``: one key."""
    key = _DURATIONS.get(event)
    if key is None:
        return
    fun = str(kwargs.get("fun_name", ""))
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]
    with _lock:
        per_fun = _compile_s.setdefault(fun, {})
        per_fun[key] = per_fun.get(key, 0.0) + float(seconds)


def watch_compiles() -> None:
    """Registers the ``jax.monitoring`` listener behind :func:`counters`,
    once a process (``enable_compile_cache()`` calls this)."""
    import jax

    global _watching
    with _lock:
        if _watching:
            return
        _watching = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def count_geometry(kernel: str, call: str, counts: Dict[str, int]) -> None:
    """``ops/attention.py``, while a flash call is traced: what ``kernel``
    will do for a call of this shape, per batch*head (``pairs_needed``,
    ``pairs_computed``, ``grid_steps``, ``steps_fetching_dead``)."""
    with _lock:
        _geometry.setdefault(kernel, {})[call] = dict(counts)


def count_moe_layout(layer: str, **counts) -> None:
    """``models/moe.py``, while an expert layer is traced: the experts this
    chip holds (``held`` of ``of``, from ``first``), the experts a token
    takes (``top_k``), the ``tokens`` of the call and the sorted ``rows``
    its routed experts run over at a time (as many trips a step as the
    step's held pairs need: one, under routing near even)."""
    with _lock:
        _moe_layout[layer] = dict(counts)


def count_mixer_layout(layer: str, **counts) -> None:
    """``models/transformer.py``, while a described layer is traced: its
    mixer's ``kind`` (``mla``, ``full_attention``, ``conv``, ``mamba2``),
    its ``heads`` and ``kv_heads`` (attention: with ``head_dim`` and the
    ``layout`` its kernels ran in) or its ``taps`` (a Mamba-2 layer's
    ``heads``, ``head_dim``, ``state``, ``groups``, ``chunk`` too, and what
    its ``scan`` ran as: ``pallas``, the kernels of ``ops/ssd.py``), and
    the ``tokens`` of the call."""
    with _lock:
        _mixer_layout[layer] = dict(counts)


def count_ring_geometry(call: str, counts: dict) -> None:
    """``parallel/ring_attention.py``, while a ring is traced: ``n``,
    ``chunk_rows``, the ``order`` the sequence lies in, and per ring
    position the ``pairs_needed`` and the ``pairs_computed`` by its calls,
    with the largest over the mean (``max_over_mean``: 1.0 is a ring on
    which no position waits for another's kernels)."""
    with _lock:
        _ring_geometry[call] = dict(counts)


def counters() -> dict:
    """What this process counted. ``compile_s[fun_name]`` with ``trace_s``
    and ``lower_s``: the seconds JAX reported, since
    ``enable_compile_cache()``, for tracing that function and lowering it
    to a module, summed over every time it did (a compile cache shortens
    neither). ``flash_geometry[kernel][call]``: the causal geometry of
    every flash call traced so far (:func:`count_geometry`).
    ``moe_layout[layer]``: the share of every expert layer traced so far
    (:func:`count_moe_layout`). ``mixer_layout[layer]``: the mixer of every
    described layer traced so far (:func:`count_mixer_layout`).
    ``ring_geometry[call]``: what each ring
    position of every ring traced so far needs and computes
    (:func:`count_ring_geometry`)."""
    with _lock:
        return {"compile_s": {f: dict(d) for f, d in _compile_s.items()},
                "flash_geometry": {k: {c: dict(n) for c, n in d.items()}
                                   for k, d in _geometry.items()},
                "moe_layout": {k: dict(d) for k, d in _moe_layout.items()},
                "mixer_layout": {k: dict(d)
                                 for k, d in _mixer_layout.items()},
                "ring_geometry": {c: dict(d)
                                  for c, d in _ring_geometry.items()}}
