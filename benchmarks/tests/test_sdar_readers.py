"""The ``sdar_moe_lm`` family's FLOP and byte counts
(``ddbench/sdar_flops.py``) and the readers of what it adds
(``ddbench/sdar_scopes.py`` and the metric files over it; the expert
layer's dotted metrics through ``moe_scopes.py``), against the mask's
pairs counted one by one, the configuration's arithmetic and a hand-built
trace."""

import json
import os
import types

import numpy as np
import pytest
from jax.profiler import ProfileData

from ddbench import moe_flops, scopes, sdar_flops, spec, tracered
from test_tracered import _plane

CELL = "sdar-30b-a3b-ep8.s8192.b1"
CONFIG = json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                     "sdar-30b-a3b-ep8.json")))
JOB_CONFIG = dict(CONFIG, n_routed_experts=CONFIG["num_experts"])
STEP = "jit(ddstore_lm_train_step)"
MOSAIC = 'custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"'
LOOP = "f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
ATTN = f"{STEP}/jvp(TransformerLM)/checkpoint/block0/attn/"
PROGRAM = {
    "%fusion.1": (LOOP, f"{STEP}/diffusion_noise/select_n"),
    "%ddstore_flash_fwd.3": ("bf16[8]{0} " + MOSAIC,
                             ATTN + "ddstore_flash_fwd/pallas_call"),
    "%ddstore_flash_dq.4": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/transpose(jvp(TransformerLM))/block0/attn/"
        "ddstore_flash_dq/pallas_call"),
    "%ddstore_flash_dkv.5": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/transpose(jvp(TransformerLM))/block0/attn/"
        "ddstore_flash_dkv/pallas_call"),
    # another Mosaic call is not a flash kernel
    "%ddstore_moe_gmm.6": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/jvp(TransformerLM)/block0/mlp/moe/moe_dispatch/"
        "moe_experts/ddstore_moe_gmm/pallas_call"),
    "%fusion.2": (LOOP, f"{STEP}/jvp(TransformerLM)/block0/mlp/moe/"
                        "moe_dispatch/sort"),
    "%fusion.3": (LOOP, f"{STEP}/optimizer/add"),
}


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
    work, moved = sdar_flops.flash_flops_bytes_per_step(6, 1, 32, 4, 8192, 4,
                                                        128)
    job = types.SimpleNamespace(
        _compiled=types.SimpleNamespace(as_text=lambda: hlo),
        config=config, loads=loads, batch=1, seq=8192, flash_flops=work,
        flash_bytes=moved)
    return {"trace": trace, "traced_steps": steps, "job": job,
            "device_kind": "TPU v5 lite", "steps": window_steps,
            "window_s": 30.0,
            "cell": types.SimpleNamespace(dry_run=False)}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


EVENTS = [("%fusion.1", 0, 10), ("%ddstore_flash_fwd.3", 10, 110),
          ("%ddstore_flash_dq.4", 110, 200),
          ("%ddstore_flash_dkv.5", 200, 320),
          ("%ddstore_moe_gmm.6", 320, 400), ("%fusion.2", 400, 460),
          ("%fusion.3", 460, 500)]


@pytest.mark.parametrize("length", [1, 4, 8])
@pytest.mark.parametrize("window", [8, 24, 40])
def test_live_pairs_against_a_count_pair_by_pair(window, length):
    live = clean = 0
    for p in range(2 * window):
        for r in range(2 * window):
            i, j = p % window // length, r % window // length
            if p < window:
                live += (j == i) if r < window else (j < i)
            elif r >= window:
                live += j <= i
                clean += j <= i
    assert sdar_flops.live_pairs(window, length) == live \
        == window * window + window * length
    assert sdar_flops.clean_on_clean_pairs(window, length) == clean


def test_required_work_a_step_is_the_configurations_arithmetic():
    per = sdar_flops.layer_matmul_flops_per_position(CONFIG)
    assert per["q"] == per["o"] == 2 * 2048 * 4096
    assert per["kv"] == 2 * 2048 * 1024
    assert per["router"] == 2 * 2048 * 128
    assert per["expert"] == 2 * 3 * 2048 * 768
    # the issue's count of a layer's forward over 16,384 positions: 1.10e12
    # in the attention kernels, 0.62e12 in the four projections, 0.15e12 in
    # the 16 held experts, 0.01e12 in the router
    pairs = sdar_flops.live_pairs(8192, 4)
    assert pairs == 67_141_632
    assert 4 * 128 * pairs * 32 / 1e12 == pytest.approx(1.10, abs=0.005)
    assert 16384 * (per["q"] + per["kv"] + per["o"]) / 1e12 \
        == pytest.approx(0.62, abs=0.005)
    assert 16384 * per["expert"] / 1e12 == pytest.approx(0.155, abs=0.005)
    # a step: six such layers less what of the last one's clean half no
    # loss term reads, and the head over 8,192 rows; three times for the
    # backward
    whole = 6 * (4.0 * 128 * 32 * pairs + 16384 * (
        per["q"] + per["kv"] + per["o"] + per["router"] + per["expert"])) \
        + 2.0 * 8192 * 2048 * 18992
    unread = 4.0 * 128 * 32 * sdar_flops.clean_on_clean_pairs(8192, 4) \
        + 8192 * (per["q"] + per["o"] + per["router"] + per["expert"])
    step = sdar_flops.step_flops(CONFIG, 1, 8192)
    assert step == pytest.approx(3 * (whole - unread))
    assert step / 1e13 == pytest.approx(3.3, abs=0.1)
    # the held pairs of each layer from the loads, the last layer's at half
    more = sdar_flops.step_flops(
        CONFIG, 1, 8192, [16384] * 5 + [16384 + 2000]) - step
    assert more == pytest.approx(3 * 1000 * per["expert"])
    # the kernels: 18 x 128 FLOPs a live pair and head, six layers
    work, moved = sdar_flops.flash_flops_bytes_per_step(6, 1, 32, 4, 8192, 4,
                                                        128)
    assert work == 18.0 * 128 * pairs * 32 * 6
    wide, thin, stats = (16384 * 32 * 128 * 2, 16384 * 4 * 128 * 2,
                         16384 * 32 * 4)
    assert moved == 6 * (7 * wide + 8 * thin + 5 * stats)
    # the grouped products' count reads this family's keys as they are
    work, _ = moe_flops.expert_flops_bytes(JOB_CONFIG, 6 * 16384, 6)
    assert work == 3 * 6 * 2048 * 768 * 6 * 16384


def test_the_shared_flash_readers_take_two_causal_calls_a_layer():
    """``scopes.flash_kernel_work`` over the family's view: S^2 + S pairs
    and 2 S rows a layer for the mask's S^2 + 4 S over 2 S rows."""
    family = spec.load_module("families", "sdar_moe_lm")
    job = types.SimpleNamespace(
        model=family._FlashView(32, 128, 6, "bfloat16"), heads=32, seq=8192,
        batch=1)
    shared = scopes.flash_kernel_work(job)
    own, _ = sdar_flops.flash_flops_bytes_per_step(6, 1, 32, 4, 8192, 4, 128)
    total = sum(work for work, _ in shared.values())
    assert total == 18.0 * 128 * (8192 * 8192 + 8192) * 32 * 6
    assert 0 < 1 - total / own < 0.0004
    rows = 1 * 32 * 8192 * 12
    assert shared["ddstore_flash_fwd"][1] == rows * (4 * 128 * 2 + 4)


def test_the_flash_kernels_are_read_by_name(monkeypatch):
    ctx = _ctx(EVENTS)
    # the three flash kernels and no other Mosaic call
    assert _read("blockdiff_flash_ms", ctx) == pytest.approx(
        310e-9 / 2 * 1e3)
    work = ctx["job"].flash_flops
    assert _read("blockdiff_flash_roofline", ctx) == pytest.approx(
        100 * (work / 197e12) * 2 / 310e-9)
    assert _read("diffusion_noise_ms", ctx) == pytest.approx(10e-9 / 2 * 1e3)
    # the expert layer's readers answer under their dotted names
    assert _read("moe_dispatch_ms.sdar", ctx) == pytest.approx(
        60e-9 / 2 * 1e3)
    assert _read("moe_experts_ms.sdar", ctx) == pytest.approx(
        80e-9 / 2 * 1e3)
    assert _read("recompute_ms.sdar", ctx) == 0.0


def test_visited_over_live_reads_the_masked_calls_alone(monkeypatch):
    from ddstore_tpu.utils import profile

    calls = {"ddstore_flash_fwd": {
        "blockdiff4 bh32 q16384+0": dict(grid_steps=96, blocks_live=96),
        "causal bh32 q8192+0": dict(grid_steps=50)},
        "ddstore_flash_dq": {
            "blockdiff4 bh32 q16384+0": dict(grid_steps=84, blocks_live=80)},
        "ddstore_flash_dkv": {}}
    monkeypatch.setattr(profile, "counters",
                        lambda: {"flash_geometry": calls})
    ctx = _ctx(EVENTS)
    assert _read("blockdiff_visited_over_live", ctx) == pytest.approx(
        180 / 176)
    monkeypatch.setattr(profile, "counters", lambda: {
        "flash_geometry": {"ddstore_flash_fwd": calls["ddstore_flash_fwd"]
                           and {"causal bh32": dict(grid_steps=50)}}})
    assert _read("blockdiff_visited_over_live", ctx) is None


def test_expert_readers_take_the_held_share_from_the_familys_config():
    rng = np.random.default_rng(0)
    loads = [rng.integers(900, 1100, (6, 128)) for _ in range(43)]
    loads[3 + 5][:, :16] = 1024
    loads[3 + 6][:, :16] = 1024
    loads[3 + 6][:, 0] = 2048
    ctx = _ctx([("%ddstore_moe_gmm.6", 0, 500)], loads=loads)
    held = (16 * 2 + 1) * 6 * 1024
    work, moved = moe_flops.expert_flops_bytes(JOB_CONFIG, float(held), 12)
    assert _read("moe_experts_roofline.sdar", ctx) == pytest.approx(
        100 * max(work / 197e12, moved / 819e9) / 500e-9)
    assert _read("moe_load_max_over_mean.sdar", ctx) == pytest.approx(
        (1.0 + 2048 / (17 * 1024 / 16)) / 2)


def test_a_program_without_the_names_reports_nothing(monkeypatch):
    """The parent commit, another family, a dry run: nothing, no raise."""
    from ddstore_tpu.utils import profile

    mine = ("blockdiff_flash_ms", "blockdiff_flash_roofline",
            "blockdiff_visited_over_live", "diffusion_noise_ms")
    # another family's program: flash kernels, no block_length
    other = {k: v for k, v in JOB_CONFIG.items() if k != "block_length"}
    monkeypatch.setattr(profile, "counters", lambda: {
        "flash_geometry": {"ddstore_flash_fwd": {"causal": {}}}})
    ctx = _ctx(EVENTS[1:], config=other)
    for name in mine:
        assert _read(name, ctx) is None, name
    # a parent commit: no such counters at all
    monkeypatch.delattr(profile, "counters")
    assert _read("blockdiff_visited_over_live", _ctx(EVENTS)) is None
    monkeypatch.undo()
    ctx = _ctx(EVENTS)
    ctx["trace"] = None
    for name in mine[:2] + mine[3:]:
        assert _read(name, ctx) is None, name
    ctx = _ctx(EVENTS)
    ctx["cell"] = types.SimpleNamespace(dry_run=True)
    assert _read("blockdiff_visited_over_live", ctx) is None


def test_every_appended_entry_has_its_file_and_lists_the_cell():
    bench = spec.load_benchmark()
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("sdar-30b-a3b-ep8", "s8192.b1", 1)
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == list(CONFIG["reduced"])
    assert os.path.exists(os.path.join(spec.ROOT, config["file"]))
    built = spec.Cell(bench, CELL)
    assert built.family_name == "sdar_moe_lm" and callable(
        built.family().build)
    assert built.traffic["batch"] == 1 and built.traffic["seq"] == 8192
    assert callable(spec.load_module("reference", "sdar_moe_lm").loss)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "blockdiff_flash_ms", "blockdiff_flash_roofline",
        "blockdiff_visited_over_live", "diffusion_noise_ms",
        "moe_dispatch_ms.sdar", "moe_experts_ms.sdar",
        "moe_experts_roofline.sdar", "moe_load_max_over_mean.sdar",
        "recompute_ms.sdar"]
    for m in mine:
        assert m["moves"] == "tokens_per_s_per_chip"
        assert callable(spec.load_module("metrics", m["name"]).read)
    # every metric the cell reports has a reader, the shared ones included
    for group in ("end_to_end", "per_layer"):
        for name in built.metric_names(group):
            assert callable(spec.load_module("metrics", name).read), name
    # no other cell reports this one's metrics, nor this one another's
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", []) and m not in mine]
    # every limit of the text the driver reads
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
