"""The readers of the program's own names (``ddbench/scopes.py`` and the
metric files over it) against hand-built traces, for what a recorded slice
cannot show; ``test_scopes_recorded.py`` holds the v5e slice."""

import types

import pytest
from jax.profiler import ProfileData

from ddbench import flops, scopes, spec, tracered
from test_tracered import _plane

BENCH = spec.load_benchmark()
NEW = ["flash_fwd_roofline", "flash_dq_roofline", "flash_dkv_roofline",
       "block_dense_ms", "head_ms", "optimizer_ms", "unnamed_share",
       "loader_wait_share", "stage_enqueue_ms", "fetch_ms", "rendezvous_s",
       "register_s", "state_init_s", "step_trace_lower_s"]
MOSAIC = 'custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"'
STEP = "jit(ddstore_lm_train_step)"

# instruction -> (rest of the trace's name for it, op_name in the module)
PROGRAM = {
    "%ddstore_flash_fwd.8": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/jvp(TransformerLM)/block0/attn/ddstore_flash_fwd/"
        "pallas_call"),
    "%ddstore_flash_dq.2": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/transpose(jvp(TransformerLM))/block0/attn/shard_map/"
        "ring_step/cond/branch_1_fun/ddstore_flash_dq/pallas_call"),
    "%ddstore_flash_dkv.5": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/transpose(jvp(TransformerLM))/block0/attn/"
        "ddstore_flash_dkv/pallas_call"),
    # a fourth Mosaic kernel, under its own name, in the mlp
    "%ddstore_fused_norm.3": (
        "bf16[8]{0} " + MOSAIC,
        f"{STEP}/jvp(TransformerLM)/block0/mlp/ddstore_fused_norm/"
        "pallas_call"),
    # a layout copy XLA made for the kernel's operand: under its scope, not
    # the kernel
    "%copy.11": (
        "bf16[8]{0} copy(bf16[8]{0} %q)",
        f"{STEP}/jvp(TransformerLM)/block0/attn/ddstore_flash_fwd/"
        "pallas_call"),
    "%fusion.20": ("f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop",
                   f"{STEP}/jvp(TransformerLM)/block1/mlp/up/dot_general"),
    "%while.4": ("(f32[8]{0}) while((f32[8]{0}) %t), body=%b",
                 f"{STEP}/transpose(jvp(head))/while"),
    "%fusion.21": ("f32[8]{0} fusion(f32[8]{0} %y), kind=kOutput",
                   f"{STEP}/transpose(jvp(head))/while/body/closed_call/mul"),
    "%fusion.30": ("f32[8]{0} fusion(f32[8]{0} %g), kind=kLoop",
                   f"{STEP}/optimizer/add"),
    "%fusion.31": ("f32[8]{0} fusion(f32[8]{0} %g), kind=kLoop",
                   f"{STEP}/jvp(TransformerLM)/embed/embed/tok/jit(_take)/"
                   "gather"),
    "%collective-permute-done.2": (
        "bf16[4]{0} collective-permute-done((bf16[4]{0}, bf16[4]{0}) %s)",
        "ring_step/ppermute"),
    "%copy-start.9": ("(f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} "
                      "%p)", None),
}


def _name(inst, program=PROGRAM):
    return f"{inst} = {program[inst][0]}"


def _ops(chip, events):
    """An ``XLA Ops`` plane; a name's quotes escaped for the text proto."""
    return _plane(f"/device:TPU:{chip}", "XLA Ops",
                  [(n.replace('"', r'\"'), s, e) for n, s, e in events])


def _hlo_text(program=PROGRAM):
    lines = ["HloModule jit_ddstore_lm_train_step", "",
             "ENTRY %main.1 (p: f32[8]) -> f32[8] {"]
    for inst, (rest, op_name) in program.items():
        meta = f', metadata={{op_name="{op_name}" stack_frame_id=7}}' \
            if op_name else ""
        lines.append(f"  {inst} = {rest}{meta}")
    return "\n".join(lines + ["}"])


def _reduced(chip0, chip1=None, host=()):
    text = _plane("/host:CPU", "python",
                  [("bench:traced_window", 0, 1000), *host])
    text += _ops(0, [(_name(i), s, e) for i, s, e in chip0])
    if chip1 is not None:
        text += _ops(1, [(_name(i), s, e) for i, s, e in chip1])
    return tracered.reduce_profile(ProfileData.from_text_proto(text))


class _Job:
    """What the readers take from a family's job."""

    batch, heads, seq = 2, 16, 8192
    model = types.SimpleNamespace(dim=1024, layers=8,
                                  compute_dtype="bfloat16")

    def __init__(self, text):
        self._compiled = types.SimpleNamespace(as_text=lambda: text)


def _ctx(trace, steps=1, text=None, dry_run=False):
    return {"trace": trace, "traced_steps": steps,
            "job": _Job(_hlo_text() if text is None else text),
            "device_kind": "TPU v5 lite",
            "cell": types.SimpleNamespace(dry_run=dry_run)}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def test_every_operation_has_one_class_by_name():
    names = scopes.op_names(_hlo_text())
    assert "%copy-start.9" not in names and len(names) == len(PROGRAM) - 1
    want = {"%ddstore_flash_fwd.8": "ddstore_flash_fwd",
            "%ddstore_flash_dq.2": "ddstore_flash_dq",
            "%ddstore_flash_dkv.5": "ddstore_flash_dkv",
            "%ddstore_fused_norm.3": "block_dense",
            "%copy.11": "block_dense", "%fusion.20": "block_dense",
            "%while.4": "head", "%fusion.21": "head",
            "%fusion.30": "optimizer", "%fusion.31": "other_named",
            "%collective-permute-done.2": "other_named",
            "%copy-start.9": "unnamed"}
    assert {i: scopes.classify(_name(i), names) for i in PROGRAM} == want
    assert scopes.classify("not an instruction", names) == "unnamed"
    # the kernel's name is the instruction's own too: found without a module
    assert scopes.classify(_name("%ddstore_flash_dq.2"), {}) \
        == "ddstore_flash_dq"
    assert scopes.classify(_name("%ddstore_fused_norm.3"), {}) == "unnamed"


def test_a_fourth_mosaic_call_is_not_flash():
    """``flash_time_share``'s pattern takes every Mosaic call; the names do
    not."""
    trace = _reduced([("%ddstore_flash_fwd.8", 0, 100),
                      ("%ddstore_fused_norm.3", 100, 400),
                      ("%ddstore_flash_dq.2", 400, 500),
                      ("%ddstore_flash_dkv.5", 500, 600)])
    by_pattern, events = trace.seconds_matching(
        spec.load_module("metrics", "flash_time_share").FLASH_KERNELS)
    assert events == 4 and by_pattern == pytest.approx(600e-9)
    classes, _ = scopes.partition(trace, _hlo_text())
    assert sum(classes[k] for k in scopes.FLASH_KERNELS) \
        == pytest.approx(300e-9)
    assert classes["block_dense"] == pytest.approx(300e-9)
    ctx = _ctx(trace)
    assert _read("block_dense_ms", ctx) == pytest.approx(300e-6)
    work = scopes.flash_kernel_work(ctx["job"])
    for kernel, metric in zip(scopes.FLASH_KERNELS, NEW[:3]):
        assert _read(metric, ctx) == pytest.approx(
            100.0 * work[kernel][0] / 197e12 / 100e-9)


def test_classes_add_up_to_busy_time_and_the_rest_is_unnamed():
    """Two chips, a loop that holds its body, an operation nothing names."""
    trace = _reduced(
        [("%ddstore_flash_fwd.8", 0, 100), ("%copy.11", 100, 150),
         ("%while.4", 200, 500), ("%fusion.21", 210, 490),
         ("%fusion.30", 500, 700), ("%copy-start.9", 700, 800)],
        [("%ddstore_flash_dkv.5", 0, 300), ("%fusion.20", 300, 600),
         ("%collective-permute-done.2", 600, 900),
         ("%fusion.31", 900, 950), ("%copy-start.9", 950, 1000)])
    classes, unnamed = scopes.partition(trace, _hlo_text())
    assert set(classes) == set(scopes.CLASSES)
    ns = {k: round(v * 1e9) for k, v in classes.items()}
    assert ns == {"ddstore_flash_fwd": 100, "ddstore_flash_dq": 0,
                  "ddstore_flash_dkv": 300, "block_dense": 350, "head": 300,
                  "optimizer": 200, "other_named": 350, "unnamed": 150}
    assert sum(classes.values()) == pytest.approx(
        sum(trace.busy_s_per_device().values()))
    assert unnamed == {"%copy-start.9 copy-start": pytest.approx(150e-9)}
    ctx = _ctx(trace, steps=2)
    device_step_ms = _read("device_step_ms", ctx)
    parts = [scopes.class_ms(ctx, c) or 0.0 for c in scopes.CLASSES]
    assert sum(parts) == pytest.approx(device_step_ms)
    assert _read("head_ms", ctx) == pytest.approx(300e-6 / 4)
    assert _read("optimizer_ms", ctx) == pytest.approx(200e-6 / 4)
    assert _read("unnamed_share", ctx) == pytest.approx(150 / 1750)
    assert _read("flash_dq_roofline", ctx) is None  # no such event


def test_a_child_ending_a_nanosecond_late_is_counted_once():
    """Four chips, recorded: a branch's last operation ends 1 ns after its
    ``conditional`` (picoseconds rounded), so ``Op.own`` takes it for a
    sibling and counts it twice. Every instant has one owner here."""
    trace = _reduced([("%while.4", 0, 500), ("%fusion.21", 100, 501),
                      ("%fusion.30", 501, 600),
                      ("%ddstore_flash_fwd.8", 600, 700)])
    assert sum(o.own for o in trace.devices[0]) == 1100
    assert scopes.innermost_ns(trace.devices[0]) == [100, 401, 99, 100]
    classes, _ = scopes.partition(trace, _hlo_text())
    assert sum(classes.values()) == pytest.approx(trace.busy_s())
    assert trace.busy_s() == pytest.approx(700e-9)


def test_a_program_without_the_names_reads_nothing():
    """The parent commit: Mosaic calls named after their flax module, no
    scope. Every trace reader gives None and none raises."""
    old = {"%block5.4": ("bf16[8]{0} " + MOSAIC,
                         "jit(step)/jvp(TransformerLM)/block5/pallas_call"),
           "%fusion.20": (PROGRAM["%fusion.20"][0],
                          "jit(step)/jvp(TransformerLM)/embed/tok/gather")}
    text = (_plane("/host:CPU", "python", [("bench:traced_window", 0, 1000)])
            + _ops(0, [(_name(i, old), 100 * n, 100 * n + 50)
                       for n, i in enumerate(old)]))
    trace = tracered.reduce_profile(ProfileData.from_text_proto(text))
    assert scopes.partition(trace, _hlo_text(old)) is None
    ctx = _ctx(trace, text=_hlo_text(old))
    assert [_read(name, ctx) for name in NEW[:10]] == [None] * 10
    ctx["job"] = object()  # a family that keeps no compiled step
    assert _read("unnamed_share", ctx) is None


def test_spans_are_clipped_to_the_window():
    """wait_batch straddles both edges; fetch and stage count whole when
    begun inside, not at all when begun before."""
    window = ("bench:traced_window", 200, 1200)
    text = _plane("/host:CPU", "python", [
        window, ("ddstore:wait_batch", 100, 300),
        ("ddstore:wait_batch", 600, 650), ("ddstore:wait_batch", 1150, 1400),
        ("ddstore:fetch", 150, 450), ("ddstore:fetch", 500, 600),
        ("ddstore:fetch", 1100, 1500), ("ddstore:stage", 1300, 1350)])
    text += _ops(0, [(_name("%fusion.20"), 200, 1200)])
    trace = tracered.reduce_profile(ProfileData.from_text_proto(text))
    ctx = _ctx(trace)
    assert _read("loader_wait_share", ctx) == pytest.approx(
        (100 + 50 + 50) / 1000)
    assert _read("fetch_ms", ctx) == pytest.approx((100 + 400) / 2 * 1e-6)
    assert _read("stage_enqueue_ms", ctx) is None  # none begun inside
    text = _plane("/host:CPU", "python", [window]) \
        + _ops(0, [(_name("%fusion.20"), 200, 1200)])
    bare = _ctx(tracered.reduce_profile(ProfileData.from_text_proto(text)))
    assert _read("loader_wait_share", bare) is None  # the parent: no span


def test_kernel_work_adds_up_to_the_benchmark_s_count():
    job = _Job("")
    work = scopes.flash_kernel_work(job)
    total_flops, total_bytes = flops.flash_flops_bytes_per_step(
        8, 2, 16, 8192, 64, 2)
    assert sum(f for f, _ in work.values()) == total_flops
    assert sum(b for _, b in work.values()) == total_bytes
    assert [work[k][0] / total_flops for k in scopes.FLASH_KERNELS] \
        == [pytest.approx(x / 18) for x in (4, 6, 8)]


def test_set_up_readers_read_the_program_s_log():
    from ddstore_tpu.utils import profile

    with profile.phase("ddstore:rendezvous"):
        pass
    for nbytes in (8, 16):
        with profile.phase("ddstore:register", bytes=nbytes):
            pass
    before = [p for p in profile.phases() if p["name"] == "ddstore:register"]
    ctx = _ctx(None)
    assert _read("rendezvous_s", ctx) > 0
    assert _read("register_s", ctx) == pytest.approx(
        sum(p["end_ns"] - p["start_ns"] for p in before) * 1e-9)
    assert scopes.phase_s(ctx, "ddstore:never") is None
    profile._on_duration("/jax/core/compile/jaxpr_trace_duration", 1.5,
                         fun_name="ddstore_lm_train_step")
    profile._on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                         0.25, fun_name="jit(ddstore_lm_train_step)")
    profile._on_duration("/jax/core/compile/backend_compile_duration", 9.0,
                         fun_name="jit(ddstore_lm_train_step)")
    assert _read("step_trace_lower_s", ctx) >= 1.75
    assert scopes.trace_lower_s(ctx, "ddstore_never_jitted") is None
    dry = _ctx(None, dry_run=True)
    assert [_read(name, dry) for name in NEW] == [None] * len(NEW)


def test_the_new_entries_are_appended_with_a_reader_each():
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    assert per_layer[-len(NEW):] == NEW
    for m in BENCH["per_layer"][-len(NEW):]:
        # no list of cells: every cell reports the metric, a later one too,
        # and a reader that finds nothing there gives None
        assert "workloads" not in m
        assert callable(spec.load_module("metrics", m["name"]).read)
    for w in BENCH["workloads"]:
        cell = spec.Cell(BENCH, w["name"])
        assert set(NEW) <= set(cell.metric_names("per_layer"))
