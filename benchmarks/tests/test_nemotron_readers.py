"""The ``nemotron_h_lm`` family's FLOP and byte counts
(``ddbench/nemotron_flops.py``) and the readers of its scopes
(``ddbench/nemotron_scopes.py`` and the metric files over it; the expert
layer's dotted metrics through ``moe_scopes.py``), against the
configuration's arithmetic and a hand-built trace."""

import json
import os
import types

import numpy as np
import pytest
from jax.profiler import ProfileData

from ddbench import moe_flops, nemotron_flops, spec, tracered
from test_tracered import _plane

CELL = "nemotron3-nano-ep16.s8192"
CONFIG = json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                     "nemotron3-nano-ep16.json")))
STEP = "jit(ddstore_lm_train_step)"
MOSAIC = 'custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"'
LOOP = "f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
MIXER = f"{STEP}/jvp(TransformerLM)/checkpoint/block0/attn/mamba_mixer"
PROGRAM = {
    "%fusion.1": (LOOP, f"{MIXER}/in_proj/dot_general"),
    "%ddstore_conv_silu_fwd.3": (
        "bf16[8]{0} " + MOSAIC, f"{MIXER}/mamba_conv/short_conv/pallas_call"),
    "%fusion.2": (LOOP, f"{MIXER}/ssd/dot_general"),
    # whatever implements it: a kernel under the scope counts alike
    "%ddstore_ssd_fwd.4": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/transpose(jvp(TransformerLM))/block2/attn/mamba_mixer/ssd/"
        "pallas_call"),
    "%fusion.5": (LOOP, f"{MIXER}/norm/mul"),
    # the attention layer's mixer is not a Mamba mixer
    "%fusion.3": (LOOP, f"{STEP}/jvp(TransformerLM)/block5/attn/qkv/"
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


def _ctx(events, steps=2, loads=None, window_steps=40, config=CONFIG):
    text = _plane("/host:CPU", "python", [("bench:traced_window", 0, 1000)])
    text += _plane("/device:TPU:0", "XLA Ops", [
        (f"{i} = {PROGRAM[i][0]}".replace('"', r'\"'), s, e)
        for i, s, e in events])
    trace = tracered.reduce_profile(ProfileData.from_text_proto(text))
    hlo = _hlo_text()
    job = types.SimpleNamespace(
        _compiled=types.SimpleNamespace(as_text=lambda: hlo),
        config=config, loads=loads, batch=2, seq=8192)
    return {"trace": trace, "traced_steps": steps, "job": job,
            "device_kind": "TPU v5 lite", "steps": window_steps,
            "window_s": 30.0,
            "cell": types.SimpleNamespace(dry_run=False)}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def test_required_work_a_step_is_the_configurations_arithmetic():
    per = nemotron_flops.layer_flops_per_token(CONFIG)
    # the two projections' 77.4 M and the recurrence's 4 N P a head
    assert per["mamba"] == 2 * 2688 * 10304 + 2 * 4096 * 2688 \
        + 4 * 128 * 64 * 64
    assert per["attention"] == 2 * 2688 * 4608 + 2 * 4096 * 2688
    assert per["router"] == 2 * 2688 * 128
    assert per["shared"] == 2 * 2 * 2688 * 3712
    assert per["expert"] == 2 * 2 * 2688 * 1856        # two products, no gate
    # the issue's count: 637 M forward FLOPs a token before the scan's
    # 4 x 2.1 M, 34.6 TF a step with attention's 1.1 TF forward
    step = nemotron_flops.step_flops(CONFIG, 2, 8192)
    assert step / 3 / 16384 / 1e6 == pytest.approx(637 + 8.4 + 67.1, abs=1)
    assert step / 1e12 == pytest.approx(35.0, abs=0.1)
    assert nemotron_flops.attention_flops(CONFIG, 2, 8192) / 1e12 \
        == pytest.approx(1.1, abs=0.01)
    # twice the pairs on the held experts: 3 x 19.96 MFLOP a pair more
    more = nemotron_flops.step_flops(
        CONFIG, 2, 8192, pairs_held=2 * 4 * 768 * 8) - step
    assert more == pytest.approx(3 * 4 * 768 * 8 * per["expert"])


def test_the_scans_least_work_against_a_hand_count():
    """A token and M layer at the published widths: 4096 + 2 x 1024 + 64
    operands and 4096 results forward (10,304 elements), those and dy in
    and four cotangents out backward (16,512): 26,816 elements of 2 B,
    over 16,384 tokens and 4 layers 3.51 GB, 4.29 ms at 819 GB/s. FLOPs:
    129 x (1024 + 4096) within a chunk's lower triangle and 4 x 4096 x 128
    for its state and what it takes from before, forward; x 3."""
    work, moved = nemotron_flops.ssd_flops_bytes(CONFIG, 16384)
    assert moved == 26816 * 2 * 16384 * 4
    assert moved / 819e9 * 1e3 == pytest.approx(4.29, abs=0.01)
    assert work == 3 * (129 * 5120 + 4 * 4096 * 128) * 16384 * 4
    # under the recurrence's own 3 x 4 N P H a token and layer plus the
    # within-chunk products: no count over what a realisation must do
    assert work / 197e12 < moved / 819e9          # bytes bound it


def test_ungated_experts_count_two_products_a_pair():
    pairs, layers = 4 * 6144.0, 4
    work, moved = nemotron_flops.expert_flops_bytes(CONFIG, pairs, layers)
    assert work == 3 * 2 * (2 * 2688 * 1856) * pairs
    assert moved == 3 * layers * 8 * 2 * 2688 * 1856 * 2 \
        + 2 * pairs * (2 * 2688 + 2 * 1856) * 2
    # the stem's count, which reads the same keys, is SwiGLU's: 1.5 x
    swiglu, _ = moe_flops.expert_flops_bytes(CONFIG, pairs, layers)
    assert swiglu == pytest.approx(1.5 * work)


def test_mamba_scopes_are_read_by_name_whatever_implements_them():
    ctx = _ctx([("%fusion.1", 0, 100),
                ("%ddstore_conv_silu_fwd.3", 100, 160),
                ("%fusion.2", 160, 300), ("%ddstore_ssd_fwd.4", 300, 320),
                ("%fusion.5", 320, 350), ("%fusion.3", 350, 500),
                ("%fusion.4", 500, 540), ("%ragged-dot-none.7", 540, 700),
                ("%fusion.6", 700, 800)])
    # everything under mamba_mixer, the convolution and the scan too
    assert _read("mamba_mixer_ms", ctx) == pytest.approx(350e-9 / 2 * 1e3)
    assert _read("mamba_conv_ms", ctx) == pytest.approx(60e-9 / 2 * 1e3)
    # the fusion and the kernel under ssd
    assert _read("ssd_ms", ctx) == pytest.approx(160e-9 / 2 * 1e3)
    _, moved = nemotron_flops.ssd_flops_bytes(CONFIG, 16384)
    assert _read("ssd_roofline", ctx) == pytest.approx(
        100 * 2 * moved / 819e9 / 160e-9)
    # the expert layer's readers answer under their dotted names
    assert _read("moe_dispatch_ms.nemotron3", ctx) == pytest.approx(
        40e-9 / 2 * 1e3)
    assert _read("moe_experts_ms.nemotron3", ctx) == pytest.approx(
        160e-9 / 2 * 1e3)


def test_expert_readers_take_the_held_share_and_two_products():
    rng = np.random.default_rng(0)
    loads = [rng.integers(700, 850, (4, 128)) for _ in range(43)]
    loads[3 + 5][:, :8] = 768
    loads[3 + 6][:, :8] = 768
    loads[3 + 6][:, 0] = 1536
    ctx = _ctx([("%ragged-dot-none.7", 0, 500)], loads=loads)
    held = (8 * 2 + 1) * 4 * 768
    work, moved = nemotron_flops.expert_flops_bytes(CONFIG, float(held), 8)
    least = max(work / 197e12, moved / 819e9)
    assert _read("moe_experts_roofline.nemotron3", ctx) == pytest.approx(
        100 * least / 500e-9)
    # two thirds of what the stem's reader would say where FLOPs bound it
    stem = spec.load_module("metrics", "moe_experts_roofline").read(ctx)
    swiglu = moe_flops.expert_flops_bytes(CONFIG, float(held), 8)
    assert stem == pytest.approx(
        100 * max(swiglu[0] / 197e12, swiglu[1] / 819e9) / 500e-9)
    assert _read("moe_load_max_over_mean.nemotron3", ctx) == pytest.approx(
        (1.0 + 1536 / (9 * 768 / 8)) / 2)


def test_a_program_without_the_scopes_reports_nothing():
    """The parent commit, another family, a dry run: nothing, no raise."""
    names = ("mamba_mixer_ms", "mamba_conv_ms", "ssd_ms", "ssd_roofline")
    ctx = _ctx([("%fusion.6", 0, 100), ("%fusion.3", 100, 200)])
    for name in names + ("moe_experts_roofline.nemotron3",):
        assert _read(name, ctx) is None
    ctx["trace"] = None
    for name in names + ("moe_experts_roofline.nemotron3",):
        assert _read(name, ctx) is None
    ctx = _ctx([("%fusion.1", 0, 100)])
    ctx["job"] = types.SimpleNamespace()      # keeps no _compiled
    assert _read("mamba_mixer_ms", ctx) is None
    # another family's job under the same scopes: no count of this one's
    lfm2 = json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                       "lfm2-8b-a1b-ep4.json")))
    ctx = _ctx([("%fusion.2", 0, 100), ("%ragged-dot-none.7", 100, 200)],
               loads=[np.full((4, 32), 4096)] * 43,
               config=dict(lfm2, n_routed_experts=8))
    assert _read("ssd_roofline", ctx) is None
    assert _read("moe_experts_roofline.nemotron3", ctx) is None


def test_the_flash_view_gives_the_kernels_their_real_head_width():
    """``scopes.flash_kernel_work`` takes the head width as ``model.dim //
    job.heads``: with the hidden size it would read 84, not 128."""
    from ddbench import scopes

    family = spec.load_module("families", "nemotron_h_lm")
    view = family._FlashView(32, 128, 1, "bfloat16")
    job = types.SimpleNamespace(model=view, heads=32, batch=2, seq=8192)
    assert view.dim // job.heads == 128 != CONFIG["hidden_size"] // 32
    work = scopes.flash_kernel_work(job)
    pairs = 8192 * 8193 // 2 * 2 * 32
    assert work["ddstore_flash_fwd"][0] == 4 * 128 * pairs
    assert sum(w for w, _ in work.values()) == 18 * 128 * pairs


def test_every_appended_entry_has_its_files_and_lists_the_cell():
    bench = spec.load_benchmark()
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "mamba_mixer_ms", "ssd_ms", "mamba_conv_ms", "ssd_roofline",
        "moe_dispatch_ms.nemotron3", "moe_experts_ms.nemotron3",
        "moe_experts_roofline.nemotron3", "moe_load_max_over_mean.nemotron3"]
    assert bench["per_layer"][-8:] == mine          # appended, at the end
    for m in mine:
        assert m["moves"] == "tokens_per_s_per_chip"
        assert callable(spec.load_module("metrics", m["name"]).read)
    # the roofline's reader is its own file, not the stem's
    assert spec.load_module("metrics", "moe_experts_roofline.nemotron3") \
        is not spec.load_module("metrics", "moe_experts_roofline")
    assert bench["workloads"][-1] == dict(
        name=CELL, config="nemotron3-nano-ep16", traffic="s8192", chips=1,
        why=bench["workloads"][-1]["why"])
    entry = bench["configs"][-1]
    assert entry["name"] == "nemotron3-nano-ep16"
    assert entry["reduced"] == list(CONFIG["reduced"]) == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    # every published width is in the file, unchanged
    widths = dict(
        hidden_size=2688, mamba_num_heads=64, mamba_head_dim=64,
        ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        num_experts_per_tok=6, num_attention_heads=32, num_key_value_heads=2,
        head_dim=128, routed_scaling_factor=2.5, expand=2,
        intermediate_size=1856, n_shared_experts=1)
    assert {k: CONFIG[k] for k in widths} == widths
    assert CONFIG["published"] == dict(
        num_hidden_layers=52, n_routed_experts=128, vocab_size=131072,
        hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*"
                                "EMEMEMEM*EMEMEMEME")
    assert CONFIG["n_routed_experts"] * CONFIG["expert_parallel"]["chips"] \
        == 128
