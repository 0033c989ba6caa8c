"""The ``mla_moe_lm`` family's FLOP counts (``ddbench/moe_flops.py``) and the
readers of its scopes and load vectors (``ddbench/moe_scopes.py`` and the
five metric files over it), against the configuration's arithmetic and a
hand-built trace."""

import json
import os
import types

import numpy as np
import pytest
from jax.profiler import ProfileData

from ddbench import moe_flops, moe_scopes, spec, tracered
from test_tracered import _plane

CONFIG = json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                     "glm47-flash-ep8.json")))
STEP = "jit(ddstore_lm_train_step)"
MOSAIC = 'custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"'
PROGRAM = {
    "%fusion.1": ("f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop",
                  f"{STEP}/jvp(TransformerLM)/checkpoint/block1/mlp/moe/"
                  "moe_dispatch/sort"),
    "%fusion.2": ("f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop",
                  f"{STEP}/transpose(jvp(TransformerLM))/block1/mlp/moe/"
                  "moe_experts/mul"),
    # XLA's own grouped product: its op_name is not the program's
    "%ragged-dot-none.7": ("bf16[8]{0} " + MOSAIC, "ragged-dot-none"),
    "%ragged-dot-metadata.2": ("s32[9]{0} " + MOSAIC, "ragged-dot-metadata"),
    # the shared expert: the block's dense work, under mlp alone
    "%fusion.3": ("f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop",
                  f"{STEP}/jvp(TransformerLM)/block1/mlp/moe/shared_up/"
                  "dot_general"),
    "%fusion.4": ("f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop",
                  f"{STEP}/jvp(TransformerLM)/mtp/mtp/block/mlp/moe/"
                  "moe_dispatch/gather"),
    "%fusion.5": ("f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop",
                  f"{STEP}/transpose(jvp(mtp))/head/while/body/mul"),
    "%fusion.6": ("f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop",
                  f"{STEP}/optimizer/add"),
}


def _hlo_text():
    lines = ["HloModule jit_ddstore_lm_train_step", "",
             "ENTRY %main.1 (p: f32[8]) -> f32[8] {"]
    for inst, (rest, op_name) in PROGRAM.items():
        lines.append(f'  {inst} = {rest}, metadata={{op_name="{op_name}" '
                     "stack_frame_id=7}")
    return "\n".join(lines + ["}"])


def _ctx(events, steps=2, loads=None, window_steps=40):
    text = _plane("/host:CPU", "python", [("bench:traced_window", 0, 1000)])
    text += _plane("/device:TPU:0", "XLA Ops", [
        (f"{i} = {PROGRAM[i][0]}".replace('"', r'\"'), s, e)
        for i, s, e in events])
    trace = tracered.reduce_profile(ProfileData.from_text_proto(text))
    hlo = _hlo_text()
    job = types.SimpleNamespace(
        _compiled=types.SimpleNamespace(as_text=lambda: hlo),
        config=CONFIG, loads=loads)
    return {"trace": trace, "traced_steps": steps, "job": job,
            "device_kind": "TPU v5 lite", "steps": window_steps,
            "window_s": 30.0,
            "cell": types.SimpleNamespace(dry_run=False)}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def test_required_work_a_step_is_the_configurations_arithmetic():
    per = moe_flops.layer_matmul_flops_per_token(CONFIG)
    # 2 x the parameters a token meets: 21.76 M (MLA), 62.9 M (dense MLP),
    # 9.44 M (an expert)
    assert per["mla"] == 2 * 21_757_952
    assert per["dense_mlp"] == 2 * 3 * 2048 * 10240
    assert per["expert"] == per["shared"] == 2 * 3 * 2048 * 1536
    assert per["router"] == 2 * 2048 * 64
    assert moe_flops.step_flops(CONFIG, 8, 2048) / 1e12 \
        == pytest.approx(40.85, abs=0.01)
    assert moe_flops.step_flops(CONFIG, 2, 8192) / 1e12 \
        == pytest.approx(59.40, abs=0.01)
    # twice the pairs on the held experts: 3 x 18.9 MFLOP a pair more
    more = moe_flops.step_flops(CONFIG, 8, 2048, pairs_held=2 * 5 * 8192) \
        - moe_flops.step_flops(CONFIG, 8, 2048)
    assert more == pytest.approx(3 * 5 * 8192 * per["expert"])
    work, moved = moe_flops.expert_flops_bytes(CONFIG, 5 * 8192, 5)
    assert work == 3 * 6 * 2048 * 1536 * 5 * 8192
    weights = 3 * 5 * 8 * 3 * 2048 * 1536 * 2
    assert moved == weights + 2 * 5 * 8192 * (3 * 2048 + 3 * 1536) * 2
    assert work / 197e12 > moved / 819e9         # the FLOPs bound it


def test_scopes_are_read_by_name_and_the_ragged_kernels_with_them():
    ctx = _ctx([("%fusion.1", 0, 100), ("%fusion.2", 100, 160),
                ("%ragged-dot-none.7", 160, 400),
                ("%ragged-dot-metadata.2", 400, 410),
                ("%fusion.3", 410, 500), ("%fusion.4", 500, 540),
                ("%fusion.5", 540, 600), ("%fusion.6", 600, 700)])
    assert _read("moe_dispatch_ms", ctx) == pytest.approx(140e-9 / 2 * 1e3)
    # the scope's own operations and XLA's two kinds of ragged kernel
    assert _read("moe_experts_ms", ctx) == pytest.approx(310e-9 / 2 * 1e3)
    # everything under mtp at any depth, the dispatch inside it too
    assert _read("mtp_ms", ctx) == pytest.approx(100e-9 / 2 * 1e3)
    assert _read("moe_experts_roofline", ctx) is None     # no loads kept


def test_roofline_and_imbalance_come_from_the_traced_steps_loads():
    rng = np.random.default_rng(0)
    # 3 warm-up steps and a window of 40: the profiler starts 2 s into the
    # 30 s (step 2), the traced window three iterations later (steps 5, 6)
    loads = [rng.integers(900, 1100, (5, 64)) for _ in range(43)]
    loads[3 + 5][:, :8] = 1024
    loads[3 + 6][:, :8] = 1024
    loads[3 + 6][:, 0] = 2048
    ctx = _ctx([("%ragged-dot-none.7", 0, 500)], loads=loads)
    held = moe_scopes.held_loads(ctx)
    assert held.shape == (2, 5, 8) and held.sum() == (8 * 2 + 1) * 5 * 1024
    work, moved = moe_flops.expert_flops_bytes(CONFIG, float(held.sum()), 10)
    assert _read("moe_experts_roofline", ctx) == pytest.approx(
        100 * (work / 197e12) / 500e-9)
    # step 5 is even (1.0); step 6 has one expert at twice the others
    assert _read("moe_load_max_over_mean", ctx) == pytest.approx(
        (1.0 + 2048 / (9 * 1024 / 8)) / 2)


def test_a_program_without_the_scopes_or_the_loads_reports_nothing():
    """The parent commit, another family, a dry run: nothing, and no raise."""
    ctx = _ctx([("%fusion.6", 0, 100)])
    for name in ("moe_dispatch_ms", "moe_experts_ms", "mtp_ms",
                 "moe_experts_roofline", "moe_load_max_over_mean"):
        assert _read(name, ctx) is None
    ctx["trace"] = None
    for name in ("moe_dispatch_ms", "moe_experts_roofline",
                 "moe_load_max_over_mean"):
        assert _read(name, ctx) is None
    ctx = _ctx([("%fusion.1", 0, 100)])
    ctx["job"] = types.SimpleNamespace()      # keeps no _compiled, no loads
    assert _read("moe_dispatch_ms", ctx) is None
    assert _read("moe_load_max_over_mean", ctx) is None
