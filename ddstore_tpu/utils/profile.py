"""The one tracing mechanism: names and spans on the JAX profiler's clock.

Everything the program says about where its time goes is read from one
place, a profiler trace (``.xplane.pb``), where host spans and device
operations share a timeline. No second recorder, no exporter, no switch:
the profiler being on is the switch, and an annotation outside a trace is
inert. The vocabulary (``ddstore`` prefix, or a scope of the step
``ddstore_lm_train_step``):

%(STEP_SCOPES)s
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
``ddstore:device_fetch``, ``ddstore:device_exchange``
    ``data/loader.py``. Host: the device-fetch path's ``fetch`` (plan,
    local reads, send buffers filled) and ``stage`` (the exchange over
    ICI dispatched). No reader: no cell of the benchmark runs that path.
``ddstore:rendezvous``, ``ddstore:register``, ``ddstore:state_init``
    ``rendezvous.py``, ``store.py``, ``models/transformer.py``. Set-up
    phases: ``FileGroup.__init__`` until every rank is present; one
    collective ``DDStore.add`` (``rows``, ``bytes``); ``create_train_state``.

A step scope is a ``jax.named_scope`` or a kernel's ``pallas_call(name=)``.
Both are in every operation's ``op_name`` (the compiled module's metadata;
a trace does not repeat it, so a reader joins trace and module by
instruction name; on the v5e a kernel's name is also its HLO
instruction's, ``%%ddstore_flash_fwd.8 = ... custom-call(...)``). JAX writes
the pass around the scopes:
``jit(f)/jvp(M)/block0/attn/mix_in/qkv/dot_general`` is the forward, ``jit(f)/transpose(jvp(M))/block0/...`` its transposed
side, ``.../checkpoint/rematted_computation/block0/...`` the second forward
of a block under ``nn.remat``, and what a hand-written rule computes again
inside its backward is under the program's own marker, ``recompute``.
:func:`describe` reads one ``op_name`` into its scopes and its pass
(:data:`PASSES`); :data:`STEP_SCOPES` is the list above as data.

The loader's three spans of one batch share ``batch`` (its number in the
epoch); ``fetch`` carries ``rows``, ``stage`` ``rows`` and ``bytes``
(annotation arguments: event stats in the trace).

Set-up happens before anybody starts a profiler, so :func:`phase` also
appends to a small process-wide list that :func:`phases` reads back in
epoch nanoseconds; ``profile_start_time`` of the trace's ``Task
Environment`` plane plus an event's ``start_ns`` is the same clock (within
40 us on the v5e host, PERF.md), so a phase, or a ddtrace event (same
``CLOCK_MONOTONIC``), can be laid beside a trace. The log keeps the newest
1024 phases (a process makes a few, and one a ``DDStore.add``); a phase
closed while a JAX backend is up also says what each local device's memory
held then (``memory``: ``state_init`` says what the state takes).
:func:`counters` holds the seconds ``jax.monitoring`` reports for tracing
each jitted function, lowering it to a module and compiling that or reading
it from the cache, with the cache's hits and misses
(``utils.enable_compile_cache`` registers the listeners; the benchmark's
``step_trace_lower_s`` and ``programs_compiled`` read them), what the
program counted while it was traced (kernel geometry, layouts, which blocks
are rematerialised) and the devices' memory now (``hbm_peak_gb``).

To look at a training run: wrap a few steady steps in :func:`trace` (or
``python3 benchmarks/run.py --workload W --trace 1 --keep-trace FILE`` on
the chip), open the directory with xprof / TensorBoard, or read the
``.xplane.pb`` with ``jax.profiler.ProfileData``: device operations are on
the ``XLA Ops`` line of each ``/device:TPU:n`` plane, the spans on the host
planes' thread lines; :func:`describe` reads the ``op_name`` of a compiled
module's instruction. ``benchmarks/ddbench/tracered.py``, ``scopes.py`` and
``passes.py`` are the reductions the benchmark's metrics use
(``benchmarks/tests`` checks them against recorded v5e slices).
"""

from __future__ import annotations

import collections
import contextlib
import re
import sys
import threading
import time
from typing import Deque, Dict, Iterator, List, Optional, Tuple

__all__ = ["trace", "annotate", "step_annotate", "phase", "phases",
           "watch_compiles", "count_geometry", "count_moe_layout",
           "count_mixer_layout", "count_ring_geometry", "count_remat",
           "count_diffusion",
           "counters", "STEP_SCOPES", "PASSES", "RECOMPUTE", "describe"]

# The names a step's operations may carry in their ``op_name``, outermost
# first where they nest, each with what emits it: the kinds of work, the
# same in every architecture. The module docstring's list is written from
# this, and tests/ holds the models to it (every name is emitted, nothing
# else is).
STEP_SCOPES = {
    "embed": "``models/transformer.py``: the token embedding (the MTP "
             "module's second one inside ``mtp``).",
    "attn": "``Block``, ``DecoderBlock``: a layer's mixer branch, norm to "
            "residual, whatever the mixer.",
    "mlp": "``Block``, ``DecoderBlock``: a layer's MLP branch, norm to "
           "residual, dense or experts.",
    "head": "``TransformerLM``, ``lm_loss``: final norm, vocabulary "
            "product and loss (the MTP term's inside ``mtp``).",
    "diffusion_noise": "``lm_loss`` of a block-diffusion arch: the step's "
                       "draw, the noised window beside the clean one and "
                       "the rows' weights. Forward only.",
    "optimizer": "``make_train_step``: the optimizer's update and the "
                 "router-bias rule. Its operations are the pass ``update``.",
    "mtp": "``TransformerLM``: around the multi-token-prediction module, "
           "its embedding, block and head pass.",
    "mix_in": "inside ``attn``: a mixer's input projection(s): ``qkv``; "
              "latent attention's ``q_a`` / ``q_b`` / ``kv_a`` / ``kv_b`` "
              "chain; ``in_proj`` (Mamba-2: with its three-way split).",
    "mix_out": "inside ``attn``: a mixer's output projection (``proj``, "
               "``out_proj``).",
    "mix_norm": "inside ``attn``: a mixer's inner norms: Mamba-2's gated "
                "group norm, q/k norms, the latent norms of ``mix_in``.",
    "conv_mixer": "inside ``attn``: ``_conv_mixer``, norm to ``W_out``.",
    "mamba_mixer": "inside ``attn``: ``_mamba2_mixer``, norm to ``W_out``.",
    "mamba_conv": "inside ``mamba_mixer``: the biased convolution and silu.",
    "short_conv": "``ops/short_conv.py``: a causal depthwise convolution, "
                  "gated or biased, both rules.",
    "ssd": "``ops/ssd.py``: the state-space scan, both rules.",
    "window": "inside ``attn``: a sliding-window layer's attention "
              "(``_gqa_mixer`` of an arch whose attention differs a layer): "
              "its flash kernels and what their rules compute around "
              "them.",
    "ring_step": "``parallel/ring_attention.py``: one step of the ring "
                 "(attend, combine, rotate).",
    "dense_mlp": "inside ``mlp``: a dense layer's up / (gate) / down "
                 "products and activation.",
    "shared_expert": "inside ``mlp``: ``SharedRoutedMoe``'s shared "
                     "expert, products and activation.",
    "moe_dispatch": "inside ``mlp``, ``models/moe.py``: router, top-k, "
                    "sorts, the gathers to and from the experts' rows (the "
                    "way back, and the way there's transpose, is the "
                    "kernel ``ddstore_moe_combine``).",
    "moe_experts": "inside ``moe_dispatch``: the grouped products (the "
                   "kernels of ``ops/moe_gmm.py``, which keep the name), "
                   "the activation between them and the held matrices' "
                   "copies in the compute type.",
    "recompute": "a marker, not a kind of work: what a hand-written rule "
                 "computes again inside its backward (``moe._routed_bwd``, "
                 "``xent._bwd``, the 1F1B schedule's stage replay). Pass "
                 "``recompute``; never among :func:`describe`'s scopes.",
    "ddstore_flash_fwd": "``ops/attention.py``, ``pallas_call(name=)``: "
                         "the flash forward, alike on one chip and in the "
                         "ring's steps.",
    "ddstore_flash_dkv": "``ops/attention.py``: the flash backward, one "
                         "kernel that writes dq, dk and dv (recomputes "
                         "the scores inside its body); the trace readers "
                         "know the backward by this name.",
    "ddstore_short_conv_fwd": "``ops/short_conv.py``: the gated "
                              "convolution, forward.",
    "ddstore_short_conv_bwd": "``ops/short_conv.py``: its backward.",
    "ddstore_conv_silu_fwd": "``ops/short_conv.py``: the biased "
                             "convolution with silu, forward.",
    "ddstore_conv_silu_bwd": "``ops/short_conv.py``: its backward.",
    "ddstore_ssd_fwd": "``ops/ssd.py``: the chunked scan, forward.",
    "ddstore_ssd_bwd": "``ops/ssd.py``: its backward (computes the "
                       "output again inside its body).",
    "ddstore_moe_gmm": "``ops/moe_gmm.py``: an expert layer's sorted rows "
                       "times their experts' matrices, and the rows' "
                       "cotangent (the same kernel, the matrices "
                       "transposed).",
    "ddstore_moe_tgmm": "``ops/moe_gmm.py``: the experts' matrices' "
                        "cotangent.",
    "ddstore_moe_combine": "``ops/moe_combine.py``, inside ``moe_dispatch``: "
                           "the weighted way back from the experts' rows to "
                           "the tokens, and with unit weights the transpose "
                           "of the way there, reading the held pairs' rows "
                           "alone.",
}
# The marker on a recomputation JAX does not label (``nn.remat``'s own is
# ``rematted_computation``).
RECOMPUTE = "recompute"
# What an operation of the step is part of, by its ``op_name``.
PASSES = ("forward", "recompute", "backward", "update")

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_CALLS = ("jit", "pjit")       # ``jit(f)``: a function's name, not a scope
_ROOTS = tuple(f"{call}(" for call in _CALLS)   # a program's name begins so

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
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
# What a device's memory_stats() is read for.
_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                "peak_bytes_reserved", "bytes_limit")
_compile_s: Dict[str, Dict[str, float]] = {}
_compile_cache = {"hits": 0, "misses": 0}
_watching = False
# Kernel name -> call shape -> what ops/attention.py's geometry counts.
_geometry: Dict[str, Dict[str, Dict[str, int]]] = {}
# Expert layer (its module path) -> what models/moe.py holds and routes.
_moe_layout: Dict[str, dict] = {}
# Described layer (its module path) -> what its mixer is and works on.
_mixer_layout: Dict[str, dict] = {}
_ring_geometry: Dict[str, dict] = {}
# Block-diffusion model (its module path) -> its objective's constants.
_diffusion: Dict[str, dict] = {}
# Block (its module path) -> whether it is rematerialised, and what is saved.
_remat: Dict[str, dict] = {}

__doc__ = __doc__ % {"STEP_SCOPES": "\n".join(
    f"``{name}``\n    {what}"
    for name, what in STEP_SCOPES.items())}


def describe(op_name: str) -> Tuple[Tuple[str, ...], Optional[str]]:
    """``(scopes, pass)`` of one operation's ``op_name``, as a compiled
    module's metadata has it. ``scopes``: the names of :data:`STEP_SCOPES`
    on its path, outermost first, transformations stripped
    (``transpose(jvp(head))`` is ``head``), a name repeated by the flax
    module inside it given once (``embed/embed``), the marker left out.
    ``pass``, one of :data:`PASSES`: under ``optimizer`` ``update``; else
    computed again (``nn.remat``'s ``rematted_computation``, or the
    program's marker :data:`RECOMPUTE` not itself transposed nor inside a
    second ``transpose(``: the first is the backward the replay is part of;
    a rule of a ``jax.custom_vjp`` that a ``jax.vjp`` under the marker pulls
    back is named by the stack it is called under, ``transpose(``, and the
    stack its forward was traced under, marker and all:
    ``.../moe/transpose(block1)/mlp/moe/recompute/.../ddstore_moe_tgmm``)
    ``recompute``; else on the transposed side (a ``transpose(`` anywhere:
    a ``jax.vjp`` inside a hand-written backward writes ``jvp(`` inside it,
    and is the backward's still) ``backward``; else ``forward``. ``None``
    where the name says nothing of the program: an empty one, or one that
    XLA gave (``ragged-dot-none``), which no ``jit(`` begins."""
    # XLA joins the names of operations it merged with ';': the first's.
    parts = op_name.split(";", 1)[0].split("/")
    if not parts[0].startswith(_ROOTS):
        return (), None
    scopes, transposed, again = [], 0, False
    for part in parts[1:]:
        wrappers = []
        while (m := _WRAPPED.match(part)):
            wrappers.append(m.group(1))
            part = m.group(2)
        transposed += "transpose" in wrappers
        if part == "rematted_computation" or (
                part == RECOMPUTE and "transpose" not in wrappers
                and transposed <= 1):
            again = True
        elif part in STEP_SCOPES and part != RECOMPUTE \
                and not any(w in _CALLS for w in wrappers) \
                and scopes[-1:] != [part]:
            scopes.append(part)
    if "optimizer" in scopes:
        which = "update"
    elif again:
        which = "recompute"
    else:
        which = "backward" if transposed else "forward"
    return tuple(scopes), which


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a JAX profiler trace of the enclosed block into
    ``logdir`` (TensorBoard ``plugins/profile`` layout)."""
    import jax

    jax.profiler.start_trace(logdir)
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
    process-wide phase log, closed even when the block raises, with the
    devices' memory as it closes where a backend is up (never brought up
    for this). Usable as context manager or decorator, from any thread;
    phases may nest (the log says so by their times alone)."""
    entry = {"name": name, "start": time.perf_counter_ns(), "end": None,
             "counts": dict(counts), "memory": None}
    with _lock:
        _phases.append(entry)
    span = annotate(name, **counts) if "jax" in sys.modules \
        else contextlib.nullcontext()
    try:
        with span:
            yield
    finally:
        entry["memory"] = _memory()
        entry["end"] = time.perf_counter_ns()


def _memory() -> Optional[Dict[str, Dict[str, int]]]:
    """``{device: {key: bytes}}`` for :data:`_MEMORY_KEYS`, of the local
    devices whose runtime counts them (a TPU's; the CPU's does not, and is
    left out), or ``None`` where no backend is up. Asking never brings one
    up: a data-only owner has not imported JAX, and rank 0 opens its store
    (``ddstore:rendezvous``, ``ddstore:register``) before it chooses its
    platform."""
    if "jax" not in sys.modules:
        return None
    import jax
    from jax._src import xla_bridge

    # JAX's own test (an internal name: where a later JAX has none, the
    # memory goes unread rather than a backend be brought up by asking).
    up = getattr(xla_bridge, "backends_are_initialized", None)
    if up is None or not up():
        return None
    out = {}
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if stats:
            out[str(dev)] = {k: int(stats[k]) for k in _MEMORY_KEYS
                             if k in stats}
    return out


def phases() -> List[dict]:
    """The closed phases of this process (the newest 1024), in the order
    they began: ``name``, ``start_ns`` and ``end_ns`` in epoch nanoseconds
    (a trace's ``profile_start_time`` + an event's ``start_ns`` is the same
    clock), ``counts`` and, where a JAX backend was up as the phase closed,
    ``memory``: per local device that counts them, ``bytes_in_use``,
    ``peak_bytes_in_use``, ``bytes_reserved``, ``peak_bytes_reserved`` and
    ``bytes_limit`` at that moment."""
    mono0, epoch0 = _CLOCK_PAIR
    with _lock:
        snap = list(_phases)
    out = []
    for e in snap:
        if e["end"] is None:
            continue
        out.append({"name": e["name"],
                    "start_ns": epoch0 + e["start"] - mono0,
                    "end_ns": epoch0 + e["end"] - mono0,
                    "counts": dict(e["counts"])})
        if e["memory"] is not None:
            out[-1]["memory"] = {d: dict(m) for d, m in e["memory"].items()}
    return out


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    """Sums trace, lowering and backend seconds per ``fun_name``. JAX
    reports tracing under ``f``, the others under ``jit(f)``: one key."""
    key = _DURATIONS.get(event)
    if key is None:
        return
    fun = str(kwargs.get("fun_name", ""))
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]
    with _lock:
        per_fun = _compile_s.setdefault(fun, {})
        per_fun[key] = per_fun.get(key, 0.0) + float(seconds)


def _on_event(event: str, **kwargs) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        with _lock:
            _compile_cache[key] += 1


def watch_compiles() -> None:
    """Registers the two ``jax.monitoring`` listeners behind
    :func:`counters`, once a process (``enable_compile_cache()`` calls
    this)."""
    import jax

    global _watching
    with _lock:
        if _watching:
            return
        _watching = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def count_geometry(kernel: str, call: str, counts: Dict[str, int]) -> None:
    """``ops/attention.py``, while a flash call is traced: what ``kernel``
    will do for a call of this shape, per batch*head (``pairs_needed``,
    ``pairs_computed``, ``grid_steps``, ``steps_fetching_dead``; a call
    under the block-diffusion mask, ``blockdiff<B>`` in its key, or under a
    sliding window, ``window<W>``: the mask's or the window's live pairs,
    the blocks its grid visits as ``grid_steps`` and, of them,
    ``blocks_live`` that hold a live pair)."""
    with _lock:
        _geometry.setdefault(kernel, {})[call] = dict(counts)


def count_moe_layout(layer: str, **counts) -> None:
    """``models/moe.py``, while an expert layer is traced: the experts this
    chip holds (``held`` of ``of``, from ``first``), the experts a token
    takes (``top_k``), the ``tokens`` of the call and the sorted ``rows``
    its routed experts run over at a time (as many trips a step as the
    step's held pairs need: one, under routing near even), the router's
    ``scoring`` (``sigmoid`` or ``softmax``), the experts' ``activation``
    (``swiglu``, ``reglu``, ``relu2``), whether the router's logits were
    handed in (``early_router``: taken before attention); what its grouped
    ``products`` ran as (``pallas``, the kernels of ``ops/moe_gmm.py``) and
    its way back from the experts' rows, ``combine`` (``pallas``,
    ``ops/moe_combine.py``'s kernel),
    their ``tiles`` (``in`` / ``out`` of the experts' width, each ``{gmm,
    gmm_t, tgmm: (tm, tk, tn)}``) and, where the experts' width was padded
    to whole lane tiles, ``padded_to``."""
    with _lock:
        _moe_layout[layer] = dict(counts)


def count_mixer_layout(layer: str, **counts) -> None:
    """``models/mixers.py``, while a described layer is traced: its
    mixer's ``kind`` (``mla``, ``full_attention``, ``conv``, ``mamba2``),
    its ``heads`` and ``kv_heads`` (attention: with ``head_dim`` and the
    ``layout`` its kernels ran in) or its ``taps`` (a Mamba-2 layer's
    ``heads``, ``head_dim``, ``state``, ``groups``, ``chunk`` too, and what
    its ``scan`` ran as: ``pallas``, the kernels of ``ops/ssd.py``), the
    ``tokens`` of the call and, where attention ran under another mask than
    the causal, that ``mask``; and an attention layer's ``window`` (None:
    causal) and whether it is ``rotary``."""
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


def count_diffusion(model: str, **counts) -> None:
    """``models/transformer.py``, while a block-diffusion loss is traced:
    ``block_length``, the ``window``'s and the trunk's ``positions``,
    ``noise_low``, ``mask_token``, ``noise_seed``. What a step drew is a
    function of these and its number
    (``transformer.diffusion_noise(arch, diffusion_key(arch, step), ...)``)
    and is recorded nowhere: the step's shapes do not follow it."""
    with _lock:
        _diffusion.setdefault(model, {}).update(counts)


def count_remat(block: str, remat: bool, policy: Optional[str]) -> None:
    """``models/transformer.py``, where ``nn.remat`` is or is not put
    around a block, while the model is traced: whether ``block`` (its
    module path) is rematerialised, the ``policy``'s name and the
    residuals ``saved`` by name (``names:flash_out,flash_lse``: the flash
    forward's output and statistics, so that kernel is not among what the
    pass ``recompute`` holds)."""
    saved = policy[len("names:"):].split(",") \
        if remat and policy and policy.startswith("names:") else []
    with _lock:
        _remat[block] = {"remat": bool(remat),
                         "policy": policy if remat else None,
                         "saved": saved}


def counters() -> dict:
    """What this process counted. ``compile_s[fun_name]`` with ``trace_s``,
    ``lower_s`` and ``backend_s``: the seconds JAX reported, since
    ``enable_compile_cache()``, for tracing that function, lowering it to
    a module and handing that to the backend (a compile, or the cache
    read in its place), summed over every time it did (a compile cache
    shortens only the last). ``compile_cache``: the persistent cache's
    ``hits`` and ``misses`` (programs compiled and written) since then.
    ``remat[block]``: which blocks are rematerialised and what they save
    (:func:`count_remat`). ``memory[device]``, only where a JAX backend is
    up (the key is absent otherwise, and asking brings none up): what each
    local device's runtime counts now, ``bytes_in_use``,
    ``peak_bytes_in_use``, ``bytes_reserved``, ``peak_bytes_reserved``,
    ``bytes_limit`` (live arrays are "in use", a running program's
    temporaries "reserved"). ``flash_geometry[kernel][call]``: the causal
    geometry of every flash call traced so far (:func:`count_geometry`).
    ``moe_layout[layer]``: the share of every expert layer traced so far
    (:func:`count_moe_layout`). ``mixer_layout[layer]``: the mixer of every
    described layer traced so far (:func:`count_mixer_layout`).
    ``ring_geometry[call]``: what each ring
    position of every ring traced so far needs and computes
    (:func:`count_ring_geometry`). ``diffusion[model]``: the objective of
    every block-diffusion model traced so far and the draws asked about
    (:func:`count_diffusion`)."""
    memory = _memory()
    with _lock:
        out = {"compile_s": {f: dict(d) for f, d in _compile_s.items()},
               "compile_cache": dict(_compile_cache),
               "remat": {k: dict(d, saved=list(d["saved"]))
                         for k, d in _remat.items()},
               "flash_geometry": {k: {c: dict(n) for c, n in d.items()}
                                  for k, d in _geometry.items()},
               "moe_layout": {k: dict(d) for k, d in _moe_layout.items()},
               "mixer_layout": {k: dict(d)
                                for k, d in _mixer_layout.items()},
               "ring_geometry": {c: dict(d)
                                 for c, d in _ring_geometry.items()},
               "diffusion": {k: dict(d) for k, d in _diffusion.items()}}
    if memory is not None:
        out["memory"] = memory
    return out
