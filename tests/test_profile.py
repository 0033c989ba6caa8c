"""Profiler integration: a trace block must produce an XProf artifact and
the annotated data-layer spans must not perturb results (annotations are
no-ops without an active trace)."""

import collections
import glob
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from ddstore_tpu import DDStore, SingleGroup
from ddstore_tpu.data import DeviceLoader, DistributedSampler, ShardedDataset
from ddstore_tpu.utils import (PipelineMetrics, annotate, profile,
                               step_annotate, trace)


def test_trace_produces_artifact(tmp_path):
    logdir = str(tmp_path / "prof")
    with trace(logdir):
        with step_annotate(0):
            x = jnp.arange(1024.0)
            jax.block_until_ready(jnp.dot(x, x))
        with annotate("host-phase"):
            np.arange(10).sum()
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, f"no trace artifact under {logdir}"


def test_annotated_loader_runs_without_trace():
    # The loader annotates fetch/stage unconditionally; with no active
    # trace this must be free and correct.
    with DDStore(SingleGroup(), backend="local") as store:
        data = np.arange(64 * 4, dtype=np.float32).reshape(64, 4)
        ds = ShardedDataset(store, data)
        loader = DeviceLoader(ds, DistributedSampler(64, 1, 0),
                              batch_size=16, mesh=None)
        batches = list(loader)
        assert len(batches) == 4
        total = np.concatenate(batches)
        np.testing.assert_array_equal(np.sort(total, axis=0), data)


# -- the program's own names and spans (ISSUE 25) ---------------------------


def _events(logdir, prefix):
    """``{name: [stats, ...]}`` of the host events under ``logdir`` whose
    name starts with ``prefix``, and the trace's epoch anchor."""
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    found, anchor = {}, None
    for plane in ProfileData.from_file(path).planes:
        stats = dict(plane.stats)
        anchor = stats.get("profile_start_time", anchor)
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    found.setdefault(ev.name, []).append(
                        dict(ev.stats, start_ns=ev.start_ns))
    return found, anchor


def test_loader_spans_share_a_batch_number(tmp_path):
    """Three batches in a trace: each has its wait, fetch and stage span,
    joined by ``batch``, with the counts taken at the boundary."""
    logdir = str(tmp_path / "prof")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))
    with DDStore(SingleGroup(), backend="local") as store:
        data = np.arange(48 * 4, dtype=np.float32).reshape(48, 4)
        ds = ShardedDataset(store, data)
        loader = DeviceLoader(ds, DistributedSampler(48, 1, 0),
                              batch_size=16, mesh=mesh)
        with trace(logdir):
            assert len(list(loader)) == 3
    found, _ = _events(logdir, "ddstore:")
    for name in ("ddstore:wait_batch", "ddstore:fetch", "ddstore:stage"):
        assert sorted(s["batch"] for s in found[name]) == [0, 1, 2], name
    assert {s["rows"] for s in found["ddstore:fetch"]} == {16}
    assert {(s["rows"], s["bytes"]) for s in found["ddstore:stage"]} \
        == {(16, 16 * 4 * 4)}


def test_phase_nests_counts_and_survives_an_exception():
    before = len(profile.phases())
    with profile.phase("ddstore:test_outer", bytes=7):
        with pytest.raises(ValueError):
            with profile.phase("ddstore:test_inner", rows=3):
                raise ValueError("inside")
        with profile.phase("ddstore:test_second"):
            pass
    outer, inner, second = profile.phases()[before:]
    assert [p["name"] for p in (outer, inner, second)] == [
        "ddstore:test_outer", "ddstore:test_inner", "ddstore:test_second"]
    assert outer["counts"] == {"bytes": 7}
    assert inner["counts"] == {"rows": 3} and second["counts"] == {}
    # nesting is in the times: both inner phases lie inside the outer one
    assert outer["start_ns"] <= inner["start_ns"] <= inner["end_ns"] \
        <= second["start_ns"] <= second["end_ns"] <= outer["end_ns"]


def test_phase_decorates_and_logs_from_any_thread():
    """The decorator form makes one entry a call, on whichever thread."""
    @profile.phase("ddstore:test_decorated", rows=1)
    def work():
        return 5

    before = len(profile.phases())
    with profile.phase("ddstore:test_main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert work() == 5
    main, other, mine = profile.phases()[before:]
    assert main["name"] == "ddstore:test_main"
    assert other["name"] == mine["name"] == "ddstore:test_decorated"
    assert other["counts"] == mine["counts"] == {"rows": 1}
    assert other["end_ns"] <= mine["start_ns"]


def test_phase_log_keeps_the_newest(monkeypatch):
    """Always on, so bounded: a process that registers variables for ever
    keeps the newest entries and loses the oldest."""
    assert profile._phases.maxlen == 1024
    monkeypatch.setattr(profile, "_phases", collections.deque(maxlen=3))
    for i in range(5):
        with profile.phase("ddstore:test_many", i=i):
            pass
    assert [p["counts"]["i"] for p in profile.phases()] == [2, 3, 4]


def test_phases_are_on_the_epoch_clock_and_the_trace_s(tmp_path):
    """``phases()`` gives epoch nanoseconds, within a millisecond of
    ``time.time_ns()``; a phase inside a trace starts where the trace
    (its anchor + the event's start) says it does."""
    logdir = str(tmp_path / "prof")
    with trace(logdir):
        t0 = time.time_ns()
        with profile.phase("ddstore:test_clock"):
            pass
        t1 = time.time_ns()
    mine = [p for p in profile.phases() if p["name"] == "ddstore:test_clock"]
    assert t0 - 1_000_000 <= mine[-1]["start_ns"] <= mine[-1]["end_ns"] \
        <= t1 + 1_000_000
    found, anchor = _events(logdir, "ddstore:test_clock")
    in_trace = anchor + found["ddstore:test_clock"][-1]["start_ns"]
    assert abs(in_trace - mine[-1]["start_ns"]) < 1_000_000


def test_store_and_state_set_up_are_phases(tmp_path):
    from ddstore_tpu import FileGroup
    from ddstore_tpu.models import transformer

    before = len(profile.phases())
    group = FileGroup(str(tmp_path / "rdv"), 0, 1)
    with DDStore(group, backend="local") as store:
        store.add("x", np.zeros((8, 4), np.float32))
        store.add_ragged("r", [np.zeros((3, 2), np.float32)])
    model = transformer.TransformerLM(vocab=64, dim=32, heads=4, layers=1)
    transformer.create_train_state(jax.random.key(0), model)
    mine = profile.phases()[before:]
    names = [p["name"] for p in mine]
    assert names[0] == "ddstore:rendezvous"
    # add_ragged registers two variables through add(), one after the
    # other, so a sum over ddstore:register counts every byte once.
    reg = [p for p in mine if p["name"] == "ddstore:register"]
    assert [p["counts"]["bytes"] for p in reg] == [8 * 4 * 4, 3 * 2 * 4, 16]
    assert all(a["end_ns"] <= b["start_ns"] for a, b in zip(reg, reg[1:]))
    assert names[-1] == "ddstore:state_init"


def test_compile_counters_are_kept_per_function(monkeypatch, tmp_path):
    from ddstore_tpu.utils import enable_compile_cache

    # With the variable set the call touches no JAX config: it only starts
    # the counters, and does so once however often it is called.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == enable_compile_cache() == str(tmp_path)
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 1.0,
        fun_name="ddstore_test_once")
    assert profile.counters()["compile_s"]["ddstore_test_once"] \
        == {"trace_s": 1.0}

    def ddstore_test_counted(x):
        return x * 3 + 1

    jax.jit(ddstore_test_counted).lower(jnp.ones(5)).compile()
    mine = profile.counters()["compile_s"]["ddstore_test_counted"]
    # one key a function, whatever prefix JAX reports each stage under
    assert set(mine) == {"trace_s", "lower_s", "backend_s"}
    assert all(v > 0 for v in mine.values())
    jax.jit(ddstore_test_counted).lower(jnp.ones(6)).compile()
    again = profile.counters()["compile_s"]["ddstore_test_counted"]
    assert all(again[k] > mine[k] for k in mine)
    assert set(profile.counters()) == {"compile_s", "compile_cache", "remat",
                                       "memory", "flash_geometry",
                                       "moe_layout", "mixer_layout",
                                       "ring_geometry", "diffusion"}


def test_summary_names_what_it_measures():
    m = PipelineMetrics()
    m.epoch_start()
    m.wait.record(0.25)
    time.sleep(0.01)
    m.epoch_end()
    s = m.summary()
    assert "input_pipeline_efficiency" not in s and "device_put" not in s
    assert not hasattr(m, "efficiency")
    assert s["loader_wait_share"] == pytest.approx(
        min(1.0, 0.25 / s["total_s"]))
    assert s["stage_enqueue"]["count"] == 0
    assert PipelineMetrics().loader_wait_share == 0.0


@pytest.mark.parametrize("backend,head_dim,layout", [
    ("tpu", 128, "bshd"), ("tpu", 64, "bhsd"), ("cpu", 128, "reference")])
def test_counters_name_the_layout_attention_ran_in(monkeypatch, backend,
                                                   head_dim, layout):
    """``flash_geometry``'s call key carries the operands' layout and
    ``mixer_layout`` the one a described layer's kernels were called in:
    heads of whole lanes go sequence-major, width 64 is transposed and goes
    head-major, and off the chip the reference runs (the model asks the
    backend; the test answers, and only traces)."""
    import flax.linen as nn

    from ddstore_tpu.models import mixers, transformer as T

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    arch = T.Lfm2MoeArch(
        layer_types=("full_attention",), num_key_value_heads=1,
        conv_L_cache=3, intermediate_size=16, moe_intermediate_size=8,
        n_routed_experts=4, num_experts_per_tok=2, first_k_dense_replace=1)
    # a batch of its own, so that no other case's call has this one's key
    b, s, nh = (3 if backend == "tpu" else 5), 256, 2
    class Mixer(nn.Module):        # what a mixer reads of its block
        dim: int
        heads: int
        arch: object
        compute_dtype: object
        layer: int = 0

        @nn.compact
        def __call__(self, x, positions):
            return mixers._gqa_mixer(self, x, positions)

    mixer = Mixer(nh * head_dim, nh, arch, jnp.float32)
    x = jax.ShapeDtypeStruct((b, s, nh * head_dim), jnp.float32)
    pos = jax.ShapeDtypeStruct((b, s), jnp.int32)
    jax.eval_shape(mixer.init, jax.random.key(0), x, pos)
    # the layer's key is its module path: empty, for a module on its own
    mixed = profile.counters()["mixer_layout"][""]
    assert mixed == dict(kind="full_attention", heads=nh, kv_heads=1,
                         head_dim=head_dim, tokens=b * s, layout=layout,
                         window=None, rotary=True)
    calls = [call for call in
             profile.counters()["flash_geometry"].get("ddstore_flash_fwd", {})
             if call.startswith(f"causal bh{b * nh} q{s}+0 k{s}+0 "
                                f"d{head_dim} ")]
    if backend == "tpu":
        assert len(calls) == 1 and calls[0].endswith(f" {layout} kv{b}")
    else:
        assert not calls


# -- the step's names as a grammar, and what the program counts (ISSUE 35) --

STEP = "jit(ddstore_lm_train_step)"
LM = "jvp(TransformerLM)"


@pytest.mark.parametrize("op_name,scopes,which", [
    # as the lowered steps of the four architectures have them
    (f"{STEP}/{LM}/block0/attn/mix_in/qkv/dot_general",
     ("attn", "mix_in"), "forward"),
    (f"{STEP}/TransformerLM/block0/mlp/dense_mlp/up/dot_general",
     ("mlp", "dense_mlp"), "forward"),                   # no gradient taken
    (f"{STEP}/transpose({LM})/block1/attn/ddstore_flash_dkv/pallas_call",
     ("attn", "ddstore_flash_dkv"), "backward"),
    (f"{STEP}/transpose(jvp(head))/jit(log_softmax)/mul",
     ("head",), "backward"),
    (f"{STEP}/transpose({LM})/{LM}/checkpoint/rematted_computation/block2/"
     "attn/mamba_mixer/mix_norm/norm/mul",
     ("attn", "mamba_mixer", "mix_norm"), "recompute"),
    (f"{STEP}/transpose({LM})/{LM}/checkpoint/block1/mlp/moe/add_any",
     ("mlp",), "backward"),
    # moe._routed_bwd: the replay under the marker, its transposes beside it
    (f"{STEP}/transpose({LM})/{LM}/checkpoint/block1/mlp/moe/recompute/"
     "jvp(moe_dispatch)/moe_experts/dot_general",
     ("mlp", "moe_dispatch", "moe_experts"), "recompute"),
    (f"{STEP}/transpose({LM})/{LM}/checkpoint/block1/mlp/moe/"
     "transpose(jvp(moe_dispatch))/moe_experts/dot_general",
     ("mlp", "moe_dispatch", "moe_experts"), "backward"),
    # a custom_vjp's rule pulled back by the replay's jax.vjp: named by the
    # stack at the pull, transpose(, the stack at its forward (marker and all)
    (f"{STEP}/transpose({LM})/{LM}/checkpoint/block1/mlp/moe/"
     "transpose(block1)/mlp/moe/recompute/jvp(moe_dispatch)/moe_experts/"
     "ddstore_moe_tgmm", ("mlp", "moe_dispatch", "moe_experts",
                          "ddstore_moe_tgmm"), "backward"),
    (f"{STEP}/transpose({LM})/block1/mlp/moe/transpose(transpose({LM}))/"
     "block1/mlp/moe/recompute/jvp(moe_dispatch)/moe_experts/"
     "ddstore_moe_gmm", ("mlp", "moe_dispatch", "moe_experts",
                         "ddstore_moe_gmm"), "backward"),
    (f"{STEP}/transpose({LM})/{LM}/checkpoint/block1/mlp/moe/recompute/"
     "jvp(moe_dispatch)/moe_experts/ddstore_moe_gmm",
     ("mlp", "moe_dispatch", "moe_experts", "ddstore_moe_gmm"), "recompute"),
    # the parent's replay, unmarked: a jvp inside the transposed side
    (f"{STEP}/transpose({LM})/block1/mlp/moe/jvp(moe_dispatch)/gather",
     ("mlp", "moe_dispatch"), "backward"),
    (f"{STEP}/transpose(jvp(head))/while/body/recompute/dot_general",
     ("head",), "recompute"),
    (f"{STEP}/optimizer/add", ("optimizer",), "update"),
    (f"{STEP}/transpose({LM})/mtp/mtp/{LM}/mtp/mtp/checkpoint/block/attn/"
     f"transpose;{STEP}/optimizer/add", ("mtp", "attn"), "backward"),
    (f"{STEP}/{LM}/embed/embed/tok/jit(_take)/gather", ("embed",),
     "forward"),
    (f"{STEP}/{LM}/block0/attn/shard_map/ring_step/ddstore_flash_fwd/"
     "pallas_call", ("attn", "ring_step", "ddstore_flash_fwd"), "forward"),
    (f"{STEP}/{LM}/jit(head)/mul", (), "forward"),       # a function's name
    ("ragged-dot-none", (), None),                       # XLA's own
    ("", (), None),
], ids=["forward", "plain", "kernel-transposed", "transposed-scope",
        "rematted", "remat-backward", "marker", "marker-transposes",
        "rule-pulled-back-remat", "rule-pulled-back-plain",
        "kernel-replayed", "unmarked-replay", "xent-replay", "optimizer", "joined-names",
        "module-repeats-scope", "ring", "jit-is-no-scope", "xla-name",
        "empty"])
def test_describe_reads_scopes_and_pass(op_name, scopes, which):
    assert profile.describe(op_name) == (scopes, which)
    assert which in profile.PASSES + (None,)
    assert set(scopes) <= set(profile.STEP_SCOPES) - {profile.RECOMPUTE}


def test_the_docstring_lists_the_vocabulary_from_the_data():
    for name, what in profile.STEP_SCOPES.items():
        assert f"``{name}``\n    {what}" in profile.__doc__
    assert "%(" not in profile.__doc__
    for span in ("ddstore:device_fetch", "ddstore:device_exchange"):
        assert span in profile.__doc__
    with pytest.raises(TypeError):
        trace("/nowhere", create_perfetto_link=True)   # needed a network


class _Chip:
    """A device whose runtime counts its memory, as a TPU's does."""

    def __str__(self):
        return "TPU_0(fake)"

    def memory_stats(self):
        return {"bytes_in_use": 5, "peak_bytes_in_use": 7,
                "bytes_reserved": 2, "peak_bytes_reserved": 3,
                "bytes_limit": 11, "num_allocs": 13}


@pytest.mark.parametrize("fake", [False, True], ids=["cpu", "counting"])
def test_memory_is_read_where_a_backend_is_up(monkeypatch, fake):
    """``counters()["memory"]`` and a closed phase's ``memory``: the five
    keys of every local device that counts them (the CPU's runtime does
    not; a stand-in does)."""
    if fake:
        monkeypatch.setattr(jax, "local_devices", lambda: [_Chip()])
    jax.devices()
    with profile.phase("ddstore:test_memory"):
        pass
    for memory in (profile.counters()["memory"],
                   profile.phases()[-1]["memory"]):
        counting = [d for d in jax.local_devices() if d.memory_stats()]
        assert set(memory) == {str(d) for d in counting}
        for stats in memory.values():
            assert set(stats) == {"bytes_in_use", "peak_bytes_in_use",
                                  "bytes_reserved", "peak_bytes_reserved",
                                  "bytes_limit"}
        if fake:
            assert memory["TPU_0(fake)"]["peak_bytes_reserved"] == 3


@pytest.mark.parametrize("imports", ["", "import jax; "],
                         ids=["no-jax", "jax-imported"])
def test_asking_for_memory_brings_no_backend_up(imports):
    """A data-only owner never imports JAX, and rank 0 opens its store
    before it chooses its platform: ``phase`` and ``counters`` read the
    memory only of a backend that is already there."""
    code = imports + (
        "import sys; from ddstore_tpu.utils import profile\n"
        "with profile.phase('ddstore:test_owner'): pass\n"
        "assert 'memory' not in profile.counters()\n"
        "assert 'memory' not in profile.phases()[-1]\n"
        "if 'jax' in sys.modules:\n"
        "    from jax._src import xla_bridge\n"
        "    assert not xla_bridge.backends_are_initialized()\n"
        "else:\n"
        "    assert not any(m.startswith('jaxlib') for m in sys.modules)\n"
        "print('ok')\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0 and done.stdout.strip() == "ok", done.stderr


def test_compile_cache_counts_a_compile_and_its_re_read(tmp_path):
    """A program compiled and written is a miss, the same program read
    back a hit; its backend seconds count both."""
    from jax.experimental.compilation_cache import compilation_cache

    profile.watch_compiles()
    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()

        def ddstore_test_cached(x):
            return jnp.sin(x) * 5 + 2

        x = jnp.ones(7)      # its own small programs, before the count
        before = profile.counters()["compile_cache"]
        jax.jit(ddstore_test_cached).lower(x).compile()
        after = profile.counters()["compile_cache"]
        assert (after["misses"], after["hits"]) == (before["misses"] + 1,
                                                    before["hits"])
        first = profile.counters()["compile_s"]["ddstore_test_cached"]
        jax.clear_caches()   # the process's own; the directory keeps its
        jax.jit(ddstore_test_cached).lower(x).compile()
        again = profile.counters()["compile_cache"]
        assert (again["misses"], again["hits"]) == (after["misses"],
                                                    after["hits"] + 1)
        second = profile.counters()["compile_s"]["ddstore_test_cached"]
        assert second["backend_s"] > first["backend_s"] > 0
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("remat,policy,saved", [
    (False, None, []), (True, None, []),
    (True, "names:flash_out,flash_lse", ["flash_out", "flash_lse"]),
    (True, "dots_with_no_batch_dims_saveable", [])])
def test_remat_counter_says_what_recompute_holds(remat, policy, saved):
    from ddstore_tpu.models import transformer as T

    model = T.TransformerLM(vocab=32, dim=16, heads=2, layers=2,
                            remat=remat, remat_policy=policy)
    tok = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    jax.eval_shape(model.init, jax.random.key(0), tok, tok)
    counted = profile.counters()["remat"]
    want = {"remat": remat, "policy": policy if remat else None,
            "saved": saved}
    assert counted["block0"] == counted["block1"] == want


def test_the_benchmarks_partition_reads_a_recorded_slice_by_pass(
        monkeypatch):
    """``benchmarks/ddbench/passes.py`` (loaded by path: the benchmark is
    not a package of the program) over the slice of a v5e trace recorded
    with the program's names, ``benchmarks/tests/data``: 29.8 ms of
    ``dense-lm-d1024.s2048`` across a step boundary, the tail of a backward
    pass, the optimizer, the next step's first forward kernel."""
    import gzip
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmarks")
    monkeypatch.syspath_prepend(bench)
    spec = importlib.util.spec_from_file_location(
        "ddbench_passes_by_path", os.path.join(bench, "ddbench", "passes.py"))
    passes = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(passes)
    from ddbench import tracered

    def text(suffix):
        with gzip.open(os.path.join(bench, "tests", "data",
                                    "v5e_s2048_names_slice" + suffix),
                       "rt") as f:
            return f.read()

    reduced = tracered.reduce_profile(
        ProfileData.from_text_proto(text(".xspace.txt.gz")))
    table, unknown = passes.partition(reduced, text(".hlo.txt.gz"),
                                      profile.describe)
    assert sum(table.values()) == pytest.approx(reduced.busy_s(), abs=1e-12)
    assert reduced.busy_s() == pytest.approx(0.029831576, abs=1e-9)
    by_kind = collections.defaultdict(set)
    for (kind, which), seconds in table.items():
        assert seconds > 0 and kind in set(profile.STEP_SCOPES) | {
            passes.NO_SCOPE, passes.NO_NAME}
        by_kind[kind].add(which)
    assert by_kind["ddstore_flash_fwd"] == {"forward"}
    # the slice was recorded when dq had a kernel of its own: that name is
    # no step scope now, so its time is its enclosing block's, backward
    assert by_kind["ddstore_flash_dkv"] == {"backward"}
    assert "ddstore_flash_dq" not in by_kind
    assert "backward" in by_kind["attn"]
    assert by_kind["optimizer"] == {"update"}          # the Adam fusions
    assert by_kind[passes.NO_NAME] == {passes.UNKNOWN}
    assert "recompute" not in set().union(*by_kind.values())
    assert sum(unknown.values()) == pytest.approx(
        table[(passes.NO_NAME, passes.UNKNOWN)])
