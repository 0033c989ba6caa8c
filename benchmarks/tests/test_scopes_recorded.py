"""The readers of the program's names against a slice of a v5e trace recorded
with them (PR 25, ``dense-lm-d1024.s2048``, seed 2250000011).

``data/v5e_s2048_names_slice.xspace.txt.gz`` is 29.8 ms of the traced window,
as an XSpace text proto: chip 0's operations and the ``bench:*`` and
``ddstore:*`` host spans as recorded, the spans' arguments kept; only the
window span was cut to the slice. It crosses one step boundary (a real
12.7 us gap): the tail of a backward pass (block 0's dq and dkv kernels), the
optimizer, the next step's embedding and block 0's forward kernel, with the
loader's wait, fetch and stage spans of that moment. The trace names the
kernels itself; the scopes are only in the compiled module, so
``data/v5e_s2048_names_slice.hlo.txt.gz`` holds the module's lines for the
slice's instructions (``backend_config`` cut).
"""

import gzip
import os
import types

import pytest
from jax.profiler import ProfileData

from ddbench import scopes, spec, tracered

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_s2048_names_slice")


def _text(suffix):
    with gzip.open(DATA + suffix, "rt") as f:
        return f.read()


@pytest.fixture(scope="module")
def profile():
    return ProfileData.from_text_proto(_text(".xspace.txt.gz"))


@pytest.fixture(scope="module")
def ctx(profile):
    module = _text(".hlo.txt.gz")
    job = types.SimpleNamespace(
        batch=8, heads=16, seq=2048,
        model=types.SimpleNamespace(dim=1024, layers=8,
                                    compute_dtype="bfloat16"),
        _compiled=types.SimpleNamespace(as_text=lambda: module))
    return {"trace": tracered.reduce_profile(profile), "traced_steps": 1,
            "job": job, "device_kind": "TPU v5 lite",
            "cell": types.SimpleNamespace(dry_run=False)}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def test_the_kernels_are_found_by_name_and_by_name_only(ctx):
    trace = ctx["trace"]
    classes, _ = scopes.partition(trace, ctx["job"]._compiled.as_text())
    ns = {k: round(classes[k] * 1e9) for k in scopes.FLASH_KERNELS}
    assert ns == {"ddstore_flash_fwd": 2252318, "ddstore_flash_dq": 2254347,
                  "ddstore_flash_dkv": 3041732}
    # what flash_time_share's pattern (every Mosaic call) finds, exactly
    by_pattern, events = trace.seconds_matching(
        spec.load_module("metrics", "flash_time_share").FLASH_KERNELS)
    assert events == 3
    assert sum(ns.values()) * 1e-9 == pytest.approx(by_pattern, abs=1e-12)
    # the trace alone names them: no module text, same three
    bare, _ = scopes.partition(trace, "")
    assert {k: round(bare[k] * 1e9) for k in scopes.FLASH_KERNELS} == ns
    assert bare["unnamed"] == pytest.approx(
        trace.busy_s() - by_pattern, abs=1e-12)


def test_the_classes_add_up_to_the_recorded_busy_time(ctx):
    trace = ctx["trace"]
    assert trace.window_s == pytest.approx(0.029845828, abs=1e-12)
    classes, unnamed = scopes.partition(trace,
                                        ctx["job"]._compiled.as_text())
    assert sum(classes.values()) == pytest.approx(trace.busy_s(), abs=1e-12)
    assert trace.busy_s() == pytest.approx(0.029831576, abs=1e-9)
    ns = {k: round(v * 1e9) for k, v in classes.items()}
    assert ns["block_dense"] == 19377323 and ns["optimizer"] == 1252203
    assert ns["other_named"] == 1068848     # the embedding
    assert ns["head"] == 0                  # mid-step: not in this slice
    assert ns["unnamed"] == 584805
    assert max(unnamed, key=unnamed.get) == "%fusion.315 fusion"
    # what the names cannot see here: async copies and slices, a layout
    # copy, ConcatBitcast, and two fusions XLA left without an op_name
    assert {k.split(" ", 1)[1] for k in unnamed} == {
        "copy-start", "copy-done", "async-start", "async-done", "copy",
        "custom-call ConcatBitcast", "fusion"}


def test_every_trace_reader_against_the_slice(ctx):
    busy_ms = _read("device_step_ms", ctx)
    assert _read("block_dense_ms", ctx) == pytest.approx(19.377323)
    assert _read("optimizer_ms", ctx) == pytest.approx(1.252203)
    assert _read("head_ms", ctx) is None
    assert _read("unnamed_share", ctx) == pytest.approx(
        0.584805 / busy_ms, rel=1e-6)
    # one forward kernel of a layer against a whole step's FLOPs: what is
    # checked is the arithmetic, 4 d FLOPs a live pair at 197 TF/s
    pairs = 2048 * 2049 // 2 * 8 * 16 * 8
    assert _read("flash_fwd_roofline", ctx) == pytest.approx(
        100.0 * 4 * 64 * pairs / 197e12 / 2252318e-9)
    assert _read("flash_dq_roofline", ctx) == pytest.approx(
        100.0 * 6 * 64 * pairs / 197e12 / 2254347e-9)
    assert _read("flash_dkv_roofline", ctx) == pytest.approx(
        100.0 * 8 * 64 * pairs / 197e12 / 3041732e-9)
    # the spans: a 14.6 us wait wholly inside, a fetch and a stage begun
    # inside
    assert _read("loader_wait_share", ctx) == pytest.approx(
        14631 / 29845828)
    assert _read("fetch_ms", ctx) == pytest.approx(1.20707)
    assert _read("stage_enqueue_ms", ctx) == pytest.approx(1.00693)
    assert _read("loader_wait_share", ctx) \
        < _read("data_wait_share", {"span_s": {"next_batch": 171740e-9},
                                    "window_s": ctx["trace"].window_s})


def test_the_spans_of_a_batch_carry_its_number_and_counts(profile):
    found = {}
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ddstore:"):
                    found[ev.name] = dict(ev.stats)
    assert found == {"ddstore:wait_batch": {"batch": 19},
                     "ddstore:fetch": {"batch": 23, "rows": 8},
                     "ddstore:stage": {"batch": 23, "rows": 8,
                                       "bytes": 131072}}
