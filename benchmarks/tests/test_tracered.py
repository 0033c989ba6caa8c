"""The trace reduction against a slice of a trace recorded on the v5e, and
against hand-built traces for what one chip's slice cannot show.

``data/v5e_s2048_slice.xspace.txt.gz`` is 70 ms of the traced window of
``dense-lm-d1024.s2048`` (PR 23, seed 12): chip 0's operations and the bench
and ddstore host spans as recorded, written as an XSpace text proto; only the
window span itself was cut to the slice. It crosses one step boundary (a real
12.5 us gap) and holds one whole ``while`` loop and the head of another.
"""

import gzip
import os

import pytest
from jax.profiler import ProfileData

from ddbench import tracered

SLICE = os.path.join(os.path.dirname(__file__), "data",
                     "v5e_s2048_slice.xspace.txt.gz")
FLASH = r'custom-call\(.*custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(SLICE, "rt") as f:
        return tracered.reduce_profile(ProfileData.from_text_proto(f.read()))


def _busy_by_sweep(ops, lo, hi):
    """Independent of tracered: count open operations over sorted edges."""
    edges = []
    for _, s, e, *_ in ops:
        edges += [(max(s, lo), 1), (min(e, hi), -1)]
    busy = depth = 0
    last = lo
    for t, d in sorted(edges):
        if depth > 0:
            busy += t - last
        depth, last = depth + d, t
    return busy


def test_window_and_busy_time(recorded):
    assert recorded.window_s == pytest.approx(0.07, abs=1e-12)
    assert list(recorded.devices) == [0]
    lo, hi = recorded.window
    sweep = _busy_by_sweep(recorded.devices[0], lo, hi) * 1e-9
    assert recorded.busy_s() == pytest.approx(sweep, abs=1e-12)
    assert recorded.busy_s() == pytest.approx(0.069985577, abs=1e-9)
    # clipped: nothing reaches outside the window, though the slice does
    assert all(lo <= s < e <= hi for _, s, e, *_ in recorded.devices[0])


def test_own_time_adds_up_to_busy_time(recorded):
    """A loop's span holds its body's operations; own times partition it."""
    per_op = recorded.op_seconds()
    assert sum(per_op.values()) == pytest.approx(recorded.busy_s(), abs=1e-9)
    loops = [o for o in recorded.devices[0] if not o.leaf]
    assert len(loops) == 2
    for name, s, e, own, _ in loops:
        assert tracered.opcode(name) == "while"
        assert 0 <= own < 0.01 * (e - s)


def test_flash_kernels_are_found(recorded):
    seconds, events = recorded.seconds_matching(FLASH)
    assert events == 8
    assert seconds == pytest.approx(0.018018886, abs=1e-9)
    top = recorded.breakdown()["device_ops"]
    assert len(top) == 10
    assert top[0][0] == "%block*.3 custom-call tpu_custom_call"
    assert top[0][1] == pytest.approx(seconds, abs=1e-9)
    assert all(len(name) <= 200 for name, _ in top)


def test_idle_gaps_name_the_host_span(recorded):
    gaps = recorded.idle_gaps(5)
    assert len(gaps) == 5
    assert gaps[0][0] == "chip 0: bench:wait_step"
    assert gaps[0][1] == pytest.approx(12.549e-6, abs=1e-9)
    total = sum(s for _, s in recorded.idle_gaps(10 ** 6))
    assert total == pytest.approx(recorded.window_s - recorded.busy_s(),
                                  abs=1e-9)
    assert recorded.collective_exposed_s() == 0.0


def _plane(name, line, events):
    meta = {n: i + 1 for i, n in enumerate(dict.fromkeys(
        n for n, _, _ in events))}
    body = "".join(
        f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }} " for n, s, e in events)
    metas = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} '
        for n, i in meta.items())
    return (f'planes {{ name: "{name}" lines {{ id: 1 name: "{line}" '
            f'timestamp_ns: 0 {body}}} {metas}}} ')


AR = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}"
CP_START = ("%collective-permute-start.2 = (bf16[4]{0}, bf16[4]{0}) "
            "collective-permute-start(bf16[4]{0} %k)")
CP_DONE = ("%collective-permute-done.2 = bf16[4]{0} "
           "collective-permute-done((bf16[4]{0}, bf16[4]{0}) %s)")
FUSION = "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
COND = ("%conditional.4 = f32[8]{0} conditional(s32[] %i, f32[8]{0} %a), "
        "branch_computations={%b0, %b1}")


def test_collectives_not_hidden_behind_compute():
    """Chip 0: 100 ns of all-reduce alone, then a permute whose wait (300 ns)
    sits inside a conditional and overlaps nothing. Chip 1: an all-reduce of
    200 ns, half of it under a fusion on the same line. Window 0..1000."""
    text = (
        _plane("/host:CPU", "python", [("bench:traced_window", 0, 1000),
                                       ("bench:next_batch", 850, 1000)])
        + _plane("/device:TPU:0", "XLA Ops", [
            (FUSION, 0, 100), (AR, 100, 200), (CP_START, 200, 210),
            (COND, 210, 600), (FUSION, 220, 300), (CP_DONE, 300, 600),
            (FUSION, 600, 800)])
        + _plane("/device:TPU:1", "XLA Ops", [
            (FUSION, 0, 500), (AR, 400, 600), (FUSION, 600, 1000)])
        + _plane("/device:TPU:1", "Steps", [("0", 0, 1000)]))
    r = tracered.reduce_profile(ProfileData.from_text_proto(text))
    per = r.collective_exposed_s_per_device()
    assert per[0] == pytest.approx((100 + 10 + 300) * 1e-9)
    assert per[1] == pytest.approx(100e-9)
    assert r.collective_exposed_s() == pytest.approx(255e-9)
    assert r.busy_s_per_device() == {0: pytest.approx(800e-9),
                                     1: pytest.approx(1000e-9)}
    assert r.busy_s() == pytest.approx(900e-9)
    assert r.idle_gaps(1) == [("chip 0: bench:next_batch",
                               pytest.approx(200e-9))]
    assert tracered.is_collective(CP_DONE) and tracered.is_collective(AR)
    assert not tracered.is_collective(FUSION)
    assert tracered.label(COND) == "%conditional.4 conditional"


def test_a_trace_with_nothing_to_read_gives_nothing():
    text = _plane("/host:CPU", "python", [("bench:traced_window", 0, 10)])
    assert tracered.reduce_profile(ProfileData.from_text_proto(text)) is None
    text = _plane("/device:TPU:0", "XLA Ops", [(FUSION, 0, 10)])
    assert tracered.reduce_profile(ProfileData.from_text_proto(text)) is None
