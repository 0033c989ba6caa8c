"""The ``lfm2_moe_lm`` family's FLOP and byte counts
(``ddbench/lfm2_flops.py``) and the readers of its scopes
(``ddbench/lfm2_scopes.py`` and the three metric files over it; the expert
layer's dotted metrics through ``moe_scopes.py``), against the
configuration's arithmetic and a hand-built trace."""

import json
import os
import types

import numpy as np
import pytest
from jax.profiler import ProfileData

from ddbench import lfm2_flops, moe_flops, spec, tracered
from test_tracered import _plane

CONFIG = json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                     "lfm2-8b-a1b-ep4.json")))
# what the family's job.config answers the expert readers by
JOB_CONFIG = dict(CONFIG, n_routed_experts=CONFIG["num_experts"])
STEP = "jit(ddstore_lm_train_step)"
MOSAIC = 'custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"'
LOOP = "f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
PROGRAM = {
    "%fusion.1": (LOOP, f"{STEP}/jvp(TransformerLM)/checkpoint/block0/attn/"
                        "conv_mixer/in_proj/dot_general"),
    "%ddstore_short_conv_fwd.3": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/jvp(TransformerLM)/checkpoint/block0/attn/conv_mixer/"
        "short_conv/pallas_call"),
    "%ddstore_short_conv_bwd.4": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/transpose(jvp(TransformerLM))/block2/attn/conv_mixer/"
        "short_conv/pallas_call"),
    # whatever implements it: an XLA fusion under the scope counts alike
    "%fusion.2": (LOOP, f"{STEP}/transpose(jvp(TransformerLM))/block2/attn/"
                        "conv_mixer/short_conv/mul"),
    # the attention layer's mixer is not a conv mixer
    "%fusion.3": (LOOP, f"{STEP}/jvp(TransformerLM)/block1/attn/qkv/"
                        "dot_general"),
    "%fusion.4": (LOOP, f"{STEP}/jvp(TransformerLM)/block1/mlp/moe/"
                        "moe_dispatch/sort"),
    "%ragged-dot-none.7": ("bf16[8]{0} " + MOSAIC, "ragged-dot-none"),
    "%fusion.6": (LOOP, f"{STEP}/optimizer/add"),
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
        config=JOB_CONFIG, loads=loads, batch=4, seq=8192)
    return {"trace": trace, "traced_steps": steps, "job": job,
            "device_kind": "TPU v5 lite", "steps": window_steps,
            "window_s": 30.0,
            "cell": types.SimpleNamespace(dry_run=False)}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def test_required_work_a_step_is_the_configurations_arithmetic():
    per = lfm2_flops.layer_matmul_flops_per_token(CONFIG)
    # 2 x the parameters a token meets in the products, and the taps and
    # gates' 2 x 2048 x 5
    assert per["conv"] == 2 * (2048 * 6144 + 2048 * 2048) + 2 * 2048 * 5
    assert per["attention"] == 2 * (2048 * 3072 + 2048 * 2048)
    assert per["dense_mlp"] == 2 * 3 * 2048 * 7168
    assert per["expert"] == 2 * 3 * 2048 * 1792
    assert per["router"] == 2 * 2048 * 32
    # the issue's count: 432 M forward FLOPs a token, 42.5 TF a step
    step = lfm2_flops.step_flops(CONFIG, 4, 8192)
    assert step / 3 / 32768 / 1e6 == pytest.approx(432.5, abs=0.5)
    assert step / 1e12 == pytest.approx(42.5, abs=0.05)
    # twice the pairs on the held experts: 3 x 22.0 MFLOP a pair more
    more = lfm2_flops.step_flops(CONFIG, 4, 8192, pairs_held=2 * 4 * 32768) \
        - step
    assert more == pytest.approx(3 * 4 * 32768 * per["expert"])
    # 11 x 2048 x 2 B a token and conv layer, four of them: 1.44 GB a layer
    assert lfm2_flops.short_conv_bytes(CONFIG, 32768) \
        == 4 * 11 * 2048 * 2 * 32768
    assert lfm2_flops.short_conv_bytes(CONFIG, 32768) / 4 / 1e9 \
        == pytest.approx(1.476, abs=0.001)
    # the grouped products' count reads this family's keys as they are
    work, moved = moe_flops.expert_flops_bytes(JOB_CONFIG, 4 * 32768, 4)
    assert work == 3 * 6 * 2048 * 1792 * 4 * 32768
    assert moved == 3 * 4 * 8 * 3 * 2048 * 1792 * 2 \
        + 2 * 4 * 32768 * (3 * 2048 + 3 * 1792) * 2


def test_conv_scopes_are_read_by_name_whatever_implements_them():
    ctx = _ctx([("%fusion.1", 0, 100),
                ("%ddstore_short_conv_fwd.3", 100, 160),
                ("%ddstore_short_conv_bwd.4", 160, 300),
                ("%fusion.2", 300, 320), ("%fusion.3", 320, 500),
                ("%fusion.4", 500, 540), ("%ragged-dot-none.7", 540, 700),
                ("%fusion.6", 700, 800)])
    # everything under conv_mixer, the convolution inside it too
    assert _read("conv_mixer_ms", ctx) == pytest.approx(320e-9 / 2 * 1e3)
    # the two kernels and the fusion under short_conv
    assert _read("short_conv_ms", ctx) == pytest.approx(220e-9 / 2 * 1e3)
    moved = lfm2_flops.short_conv_bytes(CONFIG, 32768) * 2
    assert _read("short_conv_roofline", ctx) == pytest.approx(
        100 * moved / 819e9 / 220e-9)
    # the expert layer's readers answer under their dotted names
    assert _read("moe_dispatch_ms.lfm2", ctx) == pytest.approx(
        40e-9 / 2 * 1e3)
    assert _read("moe_experts_ms.lfm2", ctx) == pytest.approx(
        160e-9 / 2 * 1e3)


def test_expert_readers_take_the_held_share_from_the_familys_config():
    rng = np.random.default_rng(0)
    loads = [rng.integers(3900, 4300, (4, 32)) for _ in range(43)]
    loads[3 + 5][:, :8] = 4096
    loads[3 + 6][:, :8] = 4096
    loads[3 + 6][:, 0] = 8192
    ctx = _ctx([("%ragged-dot-none.7", 0, 500)], loads=loads)
    held = (8 * 2 + 1) * 4 * 4096
    work, _ = moe_flops.expert_flops_bytes(JOB_CONFIG, float(held), 8)
    assert _read("moe_experts_roofline.lfm2", ctx) == pytest.approx(
        100 * (work / 197e12) / 500e-9)
    assert _read("moe_load_max_over_mean.lfm2", ctx) == pytest.approx(
        (1.0 + 8192 / (9 * 4096 / 8)) / 2)


def test_a_program_without_the_scopes_reports_nothing():
    """The parent commit, another family, a dry run: nothing, no raise."""
    ctx = _ctx([("%fusion.6", 0, 100), ("%fusion.3", 100, 200)])
    for name in ("conv_mixer_ms", "short_conv_ms", "short_conv_roofline"):
        assert _read(name, ctx) is None
    ctx["trace"] = None
    for name in ("conv_mixer_ms", "short_conv_ms", "short_conv_roofline"):
        assert _read(name, ctx) is None
    ctx = _ctx([("%fusion.1", 0, 100)])
    ctx["job"] = types.SimpleNamespace()      # keeps no _compiled
    assert _read("conv_mixer_ms", ctx) is None


def test_every_appended_metric_has_a_reader_and_lists_the_cell():
    bench = spec.load_benchmark()
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["lfm2-8b-a1b-ep4.s8192.b4"]]
    assert [m["name"] for m in mine] == [
        "conv_mixer_ms", "short_conv_ms", "short_conv_roofline",
        "moe_dispatch_ms.lfm2", "moe_experts_ms.lfm2",
        "moe_experts_roofline.lfm2", "moe_load_max_over_mean.lfm2"]
    assert bench["per_layer"][-7:] == mine          # appended, at the end
    for m in mine:
        assert m["moves"] == "tokens_per_s_per_chip"
        assert callable(spec.load_module("metrics", m["name"]).read)
