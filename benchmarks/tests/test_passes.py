"""The partition of a traced window by kind of work and pass
(``ddbench/passes.py``) and the metric files over it, against the slice of a
v5e trace recorded with the program's names (``test_scopes_recorded.py``
says what it holds) and against a hand-built trace with every pass; and the
two readers of the program's counters."""

import gzip
import os
import types

import pytest
from jax.profiler import ProfileData

from ddbench import passes, spec, tracered
from ddstore_tpu.utils import profile
from test_tracered import _plane

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_s2048_names_slice")
STEP = "jit(ddstore_lm_train_step)"
BACK = f"{STEP}/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint"
LOOP = "f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
MOSAIC = 'custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"'
PROGRAM = {
    "%fusion.1": (LOOP, f"{STEP}/jvp(TransformerLM)/block0/attn/mix_in/qkv/"
                        "dot_general"),
    "%fusion.2": (LOOP, f"{BACK}/rematted_computation/block0/attn/"
                        "mamba_mixer/mix_in/in_proj/dot_general"),
    "%fusion.3": (LOOP, f"{BACK}/block0/attn/mix_out/proj/dot_general"),
    "%fusion.4": (LOOP, f"{BACK}/block1/mlp/moe/recompute/"
                        "jvp(moe_dispatch)/gather"),
    "%fusion.5": (LOOP, f"{BACK}/block1/mlp/moe/"
                        "transpose(jvp(moe_dispatch))/moe_experts/mul"),
    "%fusion.6": (LOOP, f"{BACK}/block1/mlp/moe/shared_expert/shared_up/"
                        "dot_general"),
    "%ragged-dot-none.7": ("bf16[8]{0} " + MOSAIC, "ragged-dot-none"),
    "%fusion.8": (LOOP, f"{STEP}/optimizer/add"),
    "%fusion.9": (LOOP, f"{STEP}/jvp(TransformerLM)/concatenate"),
    "%copy.10": ("f32[8]{0} copy(f32[8]{0} %y)", None),
}


def _text(suffix):
    with gzip.open(DATA + suffix, "rt") as f:
        return f.read()


def _hlo_text():
    lines = ["HloModule jit_ddstore_lm_train_step", "",
             "ENTRY %main.1 (p: f32[8]) -> f32[8] {"]
    for inst, (rest, op_name) in PROGRAM.items():
        meta = f', metadata={{op_name="{op_name}" stack_frame_id=7}}' \
            if op_name is not None else ""
        lines.append(f"  {inst} = {rest}{meta}")
    return "\n".join(lines + ["}"])


def _ctx(trace, hlo, steps, **job):
    job = types.SimpleNamespace(
        _compiled=types.SimpleNamespace(as_text=lambda: hlo), **job)
    return {"trace": trace, "traced_steps": steps, "job": job,
            "device_kind": "TPU v5 lite",
            "cell": types.SimpleNamespace(dry_run=False)}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


@pytest.fixture(scope="module")
def recorded():
    trace = tracered.reduce_profile(
        ProfileData.from_text_proto(_text(".xspace.txt.gz")))
    return _ctx(trace, _text(".hlo.txt.gz"), 1)


def test_the_recorded_slice_by_pass(recorded):
    trace = recorded["trace"]
    table, unknown = passes.partition(
        trace, recorded["job"]._compiled.as_text(), profile.describe)
    assert sum(table.values()) == pytest.approx(trace.busy_s(), abs=1e-12)
    ns = {k: round(v * 1e9) for k, v in table.items()}
    # the kernels by their own names, each in its pass and no other
    assert ns[("ddstore_flash_fwd", "forward")] == 2252318
    assert ns[("ddstore_flash_dq", "backward")] == 2254347
    assert ns[("ddstore_flash_dkv", "backward")] == 3041732
    assert {c for k, c in ns if k.startswith("ddstore_flash")} == {
        "forward", "backward"}
    # the Adam fusions are the update, and nothing else is
    assert ns[("optimizer", "update")] == 1252203
    assert [k for k, c in ns if c == "update"] == ["optimizer"]
    # the dense step keeps nothing to compute again
    assert "recompute" not in {c for _, c in ns}
    # what scopes.py counts unnamed is what has no op_name here
    assert ns[(passes.NO_NAME, passes.UNKNOWN)] == 584805
    assert sum(unknown.values()) == pytest.approx(584805e-9)
    assert max(unknown, key=unknown.get) == "%fusion.315 fusion"


def test_every_pass_reader_against_the_slice(recorded):
    busy_ms = _read("device_step_ms", recorded)
    parts = [_read(name, recorded) for name in (
        "forward_ms", "recompute_ms", "backward_ms", "pass_unknown_ms")]
    assert parts[1] == 0.0 and parts[3] == pytest.approx(0.584805)
    assert sum(parts) + 1.252203 == pytest.approx(busy_ms, abs=1e-9)
    # the slice predates the finer names: no operation under them
    assert _read("mixer_proj_ms", recorded) == 0.0
    assert _read("shared_expert_ms", recorded) == 0.0


def test_a_hand_built_window_with_every_pass(capsys):
    events = [(inst, 100 * i, 100 * i + 10 * (i + 1))
              for i, inst in enumerate(PROGRAM)]
    text = _plane("/host:CPU", "python", [("bench:traced_window", 0, 2000)])
    text += _plane("/device:TPU:0", "XLA Ops", [
        (f"{i} = {PROGRAM[i][0]}".replace('"', r'\"'), s, e)
        for i, s, e in events])
    trace = tracered.reduce_profile(ProfileData.from_text_proto(text))
    ctx = _ctx(trace, _hlo_text(), 2)
    per = 1e-9 / 2 * 1e3                       # ns of one chip -> ms a step
    assert _read("forward_ms", ctx) == pytest.approx((10 + 90) * per)
    # remat's second forward and the replay under the program's marker
    assert _read("recompute_ms", ctx) == pytest.approx((20 + 40) * per)
    assert _read("backward_ms", ctx) == pytest.approx((30 + 50 + 60) * per)
    # XLA's own name and no name at all
    assert _read("pass_unknown_ms", ctx) == pytest.approx((70 + 100) * per)
    assert _read("mixer_proj_ms", ctx) == pytest.approx((10 + 20 + 30) * per)
    assert _read("shared_expert_ms", ctx) == pytest.approx(60 * per)
    table, columns = passes.table_of(ctx)
    assert columns == profile.PASSES + (passes.UNKNOWN,)
    assert sum(table.values()) == pytest.approx(trace.busy_s())
    assert table[(passes.NO_SCOPE, "forward")] == pytest.approx(90e-9)
    assert table[("optimizer", "update")] == pytest.approx(80e-9)
    out = capsys.readouterr().out
    assert out.count("passes: ms a step and chip") == 1     # once a run
    assert "largest unknown: %copy.10 copy" in out
    assert "%ragged-dot-none.7 custom-call tpu_custom_call" in out


def test_readers_say_nothing_where_there_is_nothing_to_read(monkeypatch):
    trace = tracered.reduce_profile(ProfileData.from_text_proto(
        _plane("/host:CPU", "python", [("bench:traced_window", 0, 10)])
        + _plane("/device:TPU:0", "XLA Ops", [
            ("%fusion.1 = " + LOOP, 0, 5)])))
    names = ("forward_ms", "recompute_ms", "backward_ms", "pass_unknown_ms",
             "mixer_proj_ms", "shared_expert_ms")
    no_module = _ctx(trace, "", 1)
    no_module["job"] = types.SimpleNamespace()        # keeps no _compiled
    dry = {"trace": None, "traced_steps": 0, "job": types.SimpleNamespace(),
           "cell": types.SimpleNamespace(dry_run=True)}
    for ctx in (no_module, dry):
        assert [_read(n, ctx) for n in names] == [None] * len(names)
    assert _read("hbm_peak_gb", dry) is None
    assert _read("programs_compiled", dry) is None
    # a commit before the grammar: the program has no describe
    monkeypatch.delattr(profile, "describe")
    ctx = _ctx(trace, _hlo_text(), 1)
    assert [_read(n, ctx) for n in names] == [None] * len(names)


def test_the_counters_readers(monkeypatch, capsys):
    ctx = {"cell": types.SimpleNamespace(dry_run=False)}
    chip = {"bytes_in_use": 8_000, "peak_bytes_in_use": 8_240,
            "bytes_reserved": 0, "peak_bytes_reserved": 5_410,
            "bytes_limit": 16_909}
    monkeypatch.setattr(profile, "counters", lambda: {
        "memory": {"TPU_0": chip,
                   "TPU_1": dict(chip, peak_bytes_reserved=5_000)},
        "compile_cache": {"hits": 9, "misses": 2},
        "compile_s": {"ddstore_lm_train_step": {"trace_s": 1.0,
                                                "backend_s": 4.0}}})
    # the sum run.py prints as device.memory_peak_bytes, of the fullest chip
    assert _read("hbm_peak_gb", ctx) == pytest.approx(13_650 / 1e9)
    assert _read("programs_compiled", ctx) == 2.0
    out = capsys.readouterr().out
    assert "TPU_0: bytes_in_use 8000" in out and "backend_s 4.00" in out
    # a parent commit counts neither; the CPU's runtime counts no memory
    monkeypatch.setattr(profile, "counters", lambda: {"compile_s": {},
                                                      "memory": {}})
    assert _read("hbm_peak_gb", ctx) is None
    assert _read("programs_compiled", ctx) is None


def test_the_new_entries_name_their_cells():
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    remat = [w["name"] for w in bench["workloads"]
             if not w["name"].startswith("dense-lm")]
    assert sorted(entries["recompute_ms"]["workloads"]) == sorted(remat)
    assert sorted(entries["shared_expert_ms"]["workloads"]) == sorted(
        n for n in remat if not n.startswith("lfm2"))
    for name in ("forward_ms", "backward_ms", "pass_unknown_ms",
                 "mixer_proj_ms", "hbm_peak_gb", "programs_compiled"):
        assert "workloads" not in entries[name]
        spec.load_module("metrics", name)
    assert entries["programs_compiled"]["moves"] == "setup_s"
    assert entries["hbm_peak_gb"]["layer"] == "device"
