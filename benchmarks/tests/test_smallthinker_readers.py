"""The ``smallthinker_moe_lm`` family's FLOP and byte counts
(``ddbench/smallthinker_flops.py``) and the readers of what it adds
(``ddbench/smallthinker_scopes.py`` and the metric files over it; the
expert layer's dotted metrics through ``moe_scopes.py``), against the
window's pairs counted one by one, the configuration's arithmetic and a
hand-built trace."""

import json
import os
import types

import numpy as np
import pytest
from jax.profiler import ProfileData

from ddbench import moe_flops, scopes, smallthinker_flops as F, spec, \
    tracered
from test_tracered import _plane

CELL = "smallthinker-21b-a3b-ep4.s16384.b1"
CONFIG = json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                     "smallthinker-21b-a3b-ep4.json")))
JOB_CONFIG = dict(CONFIG, n_routed_experts=16, moe_intermediate_size=768)
STEP = "jit(ddstore_lm_train_step)"
MOSAIC = 'custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"'
LOOP = "f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
FWD = f"{STEP}/jvp(TransformerLM)/checkpoint/"
BWD = f"{STEP}/transpose(jvp(TransformerLM))/"
PROGRAM = {
    # the full layer's kernels: no window scope
    "%ddstore_flash_fwd.1": ("bf16[8]{0} " + MOSAIC,
                             FWD + "block0/attn/ddstore_flash_fwd/pallas_call"),
    "%ddstore_flash_dq.2": ("bf16[8]{0} " + MOSAIC,
                            BWD + "block0/attn/ddstore_flash_dq/pallas_call"),
    # a windowed layer's
    "%ddstore_flash_fwd.3": (
        "bf16[8]{0} " + MOSAIC,
        FWD + "block1/attn/window/ddstore_flash_fwd/pallas_call"),
    "%ddstore_flash_dq.4": (
        "bf16[8]{0} " + MOSAIC,
        BWD + "block1/attn/window/ddstore_flash_dq/pallas_call"),
    "%ddstore_flash_dkv.5": (
        "bf16[8]{0} " + MOSAIC,
        BWD + "block1/attn/window/ddstore_flash_dkv/pallas_call"),
    # what the rules compute around the kernels is under window, no kernel
    "%fusion.6": (LOOP, BWD + "block1/attn/window/reduce_sum"),
    "%ddstore_moe_gmm.7": (
        "bf16[8]{0} " + MOSAIC,
        FWD + "block1/mlp/moe/moe_dispatch/moe_experts/ddstore_moe_gmm/"
        "pallas_call"),
    # the early router, under moe_dispatch
    "%fusion.8": (LOOP, FWD + "block1/mlp/moe_dispatch/router/dot_general"),
    "%fusion.9": (LOOP, f"{STEP}/optimizer/add"),
}
EVENTS = [("%ddstore_flash_fwd.1", 0, 100), ("%ddstore_flash_dq.2", 100, 150),
          ("%ddstore_flash_fwd.3", 150, 190),
          ("%ddstore_flash_dq.4", 190, 250),
          ("%ddstore_flash_dkv.5", 250, 330), ("%fusion.6", 330, 340),
          ("%ddstore_moe_gmm.7", 340, 400), ("%fusion.8", 400, 420),
          ("%fusion.9", 420, 500)]


def _hlo_text():
    lines = ["HloModule jit_ddstore_lm_train_step", "",
             "ENTRY %main.1 (p: f32[8]) -> f32[8] {"]
    for inst, (rest, op_name) in PROGRAM.items():
        lines.append(f'  {inst} = {rest}, metadata={{op_name="{op_name}" '
                     "stack_frame_id=7}")
    return "\n".join(lines + ["}"])


def _ctx(events, steps=2, loads=None, window_steps=40, config=JOB_CONFIG):
    text = _plane("/host:CPU", "python", [("bench:traced_window", 0, 1000)])
    text += _plane("/device:TPU:0", "XLA Ops", [
        (f"{i} = {PROGRAM[i][0]}".replace('"', r'\"'), s, e)
        for i, s, e in events])
    trace = tracered.reduce_profile(ProfileData.from_text_proto(text))
    hlo = _hlo_text()
    work, moved = F.flash_flops_bytes_per_step([4096] * 3, 1, 28, 4, 16384,
                                               128)
    job = types.SimpleNamespace(
        _compiled=types.SimpleNamespace(as_text=lambda: hlo),
        config=config, loads=loads, batch=1, seq=16384, window_flops=work,
        window_bytes=moved)
    return {"trace": trace, "traced_steps": steps, "job": job,
            "device_kind": "TPU v5 lite", "steps": window_steps,
            "window_s": 30.0,
            "cell": types.SimpleNamespace(dry_run=False)}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("s", [1, 7, 64, 100])
@pytest.mark.parametrize("window", [1, 8, 40, None])
def test_window_pairs_against_a_count_pair_by_pair(s, window):
    want = sum(1 for i in range(s) for j in range(s)
               if j <= i and (window is None or i - j < window))
    assert F.window_pairs(s, window) == want


def test_required_work_a_step_is_the_configurations_arithmetic():
    per = F.layer_matmul_flops_per_position(CONFIG)
    assert per["qkv"] == 2 * 2560 * 4608 and per["o"] == 2 * 3584 * 2560
    assert per["router"] == 2 * 2560 * 64
    assert per["expert"] == 2 * 3 * 2560 * 768
    # the issue's counts: a head keeps 58,722,304 of a causal call's
    # 134,225,920 pairs under the window; forward FLOPs a step 1.92e12 in
    # the full layer's kernels, 2.53e12 in the windowed layers', 2.75e12
    # in the projections, 1.16e12 in the held experts, 3.19e12 in the head
    assert F.window_pairs(16384, 4096) == 58_722_304
    assert F.window_pairs(16384) == 134_225_920
    attention = lambda w: 4.0 * 128 * 28 * F.window_pairs(16384, w)
    assert attention(None) / 1e12 == pytest.approx(1.92, abs=0.005)
    assert 3 * attention(4096) / 1e12 == pytest.approx(2.53, abs=0.005)
    assert 4 * 16384 * (per["qkv"] + per["o"]) / 1e12 == pytest.approx(
        2.75, abs=0.005)
    assert 4 * 24576 * per["expert"] / 1e12 == pytest.approx(1.16, abs=0.005)
    assert 2.0 * 16384 * 2560 * 37984 / 1e12 == pytest.approx(3.19, abs=0.005)
    step = F.step_flops(CONFIG, 1, 16384)
    whole = attention(None) + 3 * attention(4096) + 4 * 16384 * (
        per["qkv"] + per["o"] + per["router"]) + 4 * 24576 * per["expert"] \
        + 2.0 * 16384 * 2560 * 37984
    assert step == pytest.approx(3 * whole)
    more = F.step_flops(CONFIG, 1, 16384, 4 * 24576 + 1000) - step
    assert more == pytest.approx(3 * 1000 * per["expert"])
    # the kernels: 18 x 128 FLOPs a live pair and head
    work, moved = F.flash_flops_bytes_per_step([4096] * 3, 1, 28, 4, 16384,
                                               128)
    assert work == 18.0 * 128 * 28 * 3 * 58_722_304
    assert work / 197e12 * 1e3 == pytest.approx(57.7, abs=0.1)
    wide, thin, stats = (16384 * 28 * 128 * 2, 16384 * 4 * 128 * 2,
                         16384 * 28 * 4)
    assert moved == 3 * (7 * wide + 8 * thin + 5 * stats)
    # the grouped products' count reads this family's keys as they are
    work, _ = moe_flops.expert_flops_bytes(JOB_CONFIG, 4 * 24576, 4)
    assert work == 3 * 6 * 2560 * 768 * 4 * 24576


def test_the_shared_flash_readers_take_the_mixs_own_pairs():
    """``scopes.flash_kernel_work`` over the family's view: the full
    layer's pairs and the windowed layers', 2.3125 causal calls' worth."""
    family = spec.load_module("families", "smallthinker_moe_lm")
    calls = (134_225_920 + 3 * 58_722_304) / 134_225_920
    assert calls == pytest.approx(2.3125, abs=1e-4)
    job = types.SimpleNamespace(
        model=family._FlashView(28, 128, calls, "bfloat16"), heads=28,
        seq=16384, batch=1)
    shared = scopes.flash_kernel_work(job)
    own, _ = F.flash_flops_bytes_per_step([None, 4096, 4096, 4096], 1, 28, 4,
                                          16384, 128)
    assert sum(work for work, _ in shared.values()) == pytest.approx(own)


def test_the_window_kernels_are_read_by_scope():
    ctx = _ctx(EVENTS)
    # the windowed layer's three kernels, not the full layer's, nor what
    # their rules compute beside them under the scope
    assert _read("window_flash_ms", ctx) == pytest.approx(180e-9 / 2 * 1e3)
    work = ctx["job"].window_flops
    assert _read("window_flash_roofline", ctx) == pytest.approx(
        100 * (work / 197e12) * 2 / 180e-9)
    # the early router's product is the expert layer's dispatch
    assert _read("moe_dispatch_ms.smallthinker", ctx) == pytest.approx(
        20e-9 / 2 * 1e3)
    assert _read("moe_experts_ms.smallthinker", ctx) == pytest.approx(
        60e-9 / 2 * 1e3)
    assert _read("recompute_ms.smallthinker", ctx) == 0.0


def test_visited_over_live_reads_the_window_calls_alone(monkeypatch):
    from ddstore_tpu.utils import profile

    calls = {"ddstore_flash_fwd": {
        "window4096 bh28 q16384+0": dict(grid_steps=84, blocks_live=84),
        "causal bh28 q16384+0": dict(grid_steps=144)},
        "ddstore_flash_dq": {
            "window4096 bh28 q16384+0": dict(grid_steps=72, blocks_live=70)},
        "ddstore_flash_dkv": {}}
    monkeypatch.setattr(profile, "counters",
                        lambda: {"flash_geometry": calls})
    assert _read("window_visited_over_live", _ctx(EVENTS)) == pytest.approx(
        156 / 154)


def test_expert_readers_take_the_held_share_from_the_familys_config():
    rng = np.random.default_rng(0)
    loads = [rng.integers(1400, 1700, (4, 64)) for _ in range(43)]
    loads[3 + 5][:, :16] = 1536
    loads[3 + 6][:, :16] = 1536
    ctx = _ctx([("%ddstore_moe_gmm.7", 0, 500)], loads=loads)
    held = 16 * 2 * 4 * 1536
    work, moved = moe_flops.expert_flops_bytes(JOB_CONFIG, float(held), 8)
    assert _read("moe_experts_roofline.smallthinker", ctx) == pytest.approx(
        100 * max(work / 197e12, moved / 819e9) / 500e-9)
    assert _read("moe_load_max_over_mean.smallthinker", ctx) == \
        pytest.approx(1.0)


def test_a_program_without_the_names_reports_nothing(monkeypatch):
    """The parent commit, another family, a dry run: nothing, no raise."""
    from ddstore_tpu.utils import profile

    mine = ("window_flash_ms", "window_flash_roofline",
            "window_visited_over_live")
    # another family's program: flash kernels, no window anywhere
    other = {k: v for k, v in JOB_CONFIG.items()
             if k != "sliding_window_layout"}
    monkeypatch.setattr(profile, "counters", lambda: {
        "flash_geometry": {"ddstore_flash_fwd": {"causal": {}}}})
    ctx = _ctx(EVENTS[:2], config=other)
    for name in mine:
        assert _read(name, ctx) is None, name
    # a parent commit: no such counters, no describe
    monkeypatch.delattr(profile, "counters")
    assert _read("window_visited_over_live", _ctx(EVENTS)) is None
    monkeypatch.delattr(profile, "describe")
    assert _read("window_flash_ms", _ctx(EVENTS)) is None
    monkeypatch.undo()
    ctx = _ctx(EVENTS)
    ctx["trace"] = None
    for name in mine[:2]:
        assert _read(name, ctx) is None, name
    ctx = _ctx(EVENTS)
    ctx["cell"] = types.SimpleNamespace(dry_run=True)
    assert _read("window_visited_over_live", ctx) is None


def test_every_appended_entry_has_its_file_and_lists_the_cell():
    bench = spec.load_benchmark()
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("smallthinker-21b-a3b-ep4", "s16384.b1", 1)
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert set(config["reduced"]) == set(CONFIG["reduced"])
    assert os.path.exists(os.path.join(spec.ROOT, config["file"]))
    built = spec.Cell(bench, CELL)
    assert built.family_name == "smallthinker_moe_lm" and callable(
        built.family().build)
    assert built.traffic["batch"] == 1 and built.traffic["seq"] == 16384
    assert callable(spec.load_module("reference", "smallthinker_moe_lm").loss)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "window_flash_ms", "window_flash_roofline",
        "window_visited_over_live", "moe_dispatch_ms.smallthinker",
        "moe_experts_ms.smallthinker", "moe_experts_roofline.smallthinker",
        "moe_load_max_over_mean.smallthinker", "recompute_ms.smallthinker"]
    for m in mine:
        assert m["moves"] == "tokens_per_s_per_chip"
        assert callable(spec.load_module("metrics", m["name"]).read)
    for group in ("end_to_end", "per_layer"):
        for name in built.metric_names(group):
            assert callable(spec.load_module("metrics", name).read), name
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", []) and m not in mine]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
