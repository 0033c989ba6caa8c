"""One cell of BENCHMARK.json, once, in a new process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The measured loop is the loop of ``examples/lm_longcontext.py:184-198`` and
``examples/vae_mnist.py:111-136`` through the public API only: per epoch
``set_epoch`` and a new ``DeviceLoader`` at its defaults (plus the ``spec``
the LM example passes), per step ``next(loader)`` then the train step. One
departure: the example's per-step ``float(loss)`` is a block on the loss of
step i-2, so the device queue stays fed and every step still gets a
completion time.

This process owns the chip(s). Before it touches JAX it starts the store's
other ranks as data-only owner processes (``--owner``), which build their
shard from ``--seed``, register it, serve reads and never import a model.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. ``--dry-run`` is the same path at toy sizes on the CPU:
it prints ``DRY RUN (cpu)`` and a result line whose ``metrics`` is empty.

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

from ddbench import spec  # noqa: E402

# Iterations between a block and the step it waits for.
LAG = 2
# Window seconds before the profiler starts, and iterations it is given to
# refill the device queue before the traced window opens.
TRACE_AFTER_S = 2.0
TRACE_SETTLE = LAG + 1
# Batches compared with the reference: the loader's first, and seeded random
# ones through ds.fetch after the window.
CHECK_FIRST, CHECK_AFTER = 4, 8
LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def say(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# The store: every rank runs this (registration is collective).
# ---------------------------------------------------------------------------


def open_store(cell, family, rdv, rank, seed):
    """Build this rank's shard from the seed, join the store, register it.
    Host only: no JAX backend is touched."""
    from ddstore_tpu import DDStore, FileGroup, SingleGroup

    t = cell.traffic
    arrays = family.shard(seed, rank, t, cell.config)
    world = int(t["ranks"])
    group = SingleGroup() if world == 1 else FileGroup(rdv, rank, world)
    store = DDStore(group, backend=t["backend"])
    return store, family.open_dataset(store, arrays)


def owner_main(cell, family, rdv, rank, seed):
    """Ranks 1..n-1: register, serve until rank 0 says done (or dies)."""
    parent = os.getppid()
    store, _ = open_store(cell, family, rdv, rank, seed)
    done = os.path.join(rdv, "bench.done")
    while not os.path.exists(done):
        if os.getppid() != parent:
            sys.exit(f"owner {rank}: rank 0 is gone")
        time.sleep(0.05)
    store.close()


def check_owners(owners):
    for rank, proc in enumerate(owners, start=1):
        if proc.poll() is not None:
            raise RuntimeError(f"owner {rank} exited with {proc.returncode} "
                               f"while rank 0 was still reading")


# ---------------------------------------------------------------------------
# Rank 0.
# ---------------------------------------------------------------------------


def init_device(cell):
    """The one place this process takes the chip(s)."""
    import jax

    from ddstore_tpu.utils import enable_compile_cache

    want = "cpu" if cell.dry_run else "tpu"
    jax.config.update("jax_platforms", want)
    cache = None
    if not cell.dry_run:
        cache = enable_compile_cache()
        # Every program goes to the cache, the small ones too: a warm run
        # compiles nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    if jax.default_backend() != want:
        raise RuntimeError(f"backend is {jax.default_backend()}, want {want}")
    if len(devs) < cell.chips:
        raise RuntimeError(f"cell {cell.name} needs {cell.chips} chip(s), "
                           f"JAX found {len(devs)}")
    say(f"platform={devs[0].platform} device_kind={devs[0].device_kind!r} "
        f"devices={len(devs)} (cell uses {cell.chips}) jax={jax.__version__} "
        f"compile cache: {cache}")
    return devs


class Spans:
    """The benchmark's own host spans: each is a profiler annotation (seen in
    a trace) and a sum on the host clock (seen in every run)."""

    def __init__(self):
        import jax

        self._annotation = jax.profiler.TraceAnnotation
        self.seconds = collections.Counter()

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        with self._annotation("bench:" + name):
            yield
        self.seconds[name] += time.perf_counter() - t0


class Tracer:
    """Profiles a few steps (or seconds) of the window, between iteration
    boundaries of the steady loop, into a directory of its own."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.state, self.steps, self.settle = "waiting", 0, TRACE_SETTLE
        self._span = None

    def tick(self, since_t0, steps):
        import jax

        now = time.perf_counter()
        if self.state == "waiting" and since_t0 >= TRACE_AFTER_S:
            jax.profiler.start_trace(self.dir)
            self.state = "settling"
        elif self.state == "settling":
            self.settle -= 1
            if self.settle <= 0:
                self._span = jax.profiler.TraceAnnotation(
                    "bench:traced_window")
                self._span.__enter__()
                self._t0, self._steps0, self.state = now, steps, "tracing"
        elif self.state == "tracing":
            enough = steps - self._steps0 >= self.cfg["steps"] \
                if "steps" in self.cfg \
                else now - self._t0 >= self.cfg["seconds"]
            if enough:
                self.finish(steps)

    def finish(self, steps):
        import jax

        if self.state == "tracing":
            self._span.__exit__(None, None, None)
            self.steps = steps - self._steps0
        if self.state in ("settling", "tracing"):
            jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self, keep=None):
        import glob

        from ddbench import tracered

        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found or not self.steps:
            return None
        if keep:
            os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
            shutil.copy(found[0], keep)
        return tracered.reduce_file(found[0])


def rows_differing(got, want):
    """Rows of the delivered batch (a tuple of arrays, or one) that are not
    the reference's, dtype included."""
    import numpy as np

    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    bad = np.zeros(len(want[0]), bool)
    for g, w in zip(got, want):
        g = np.asarray(g)
        if g.dtype != w.dtype or g.shape != w.shape:
            return len(bad)
        bad |= (g != w).reshape(len(w), -1).any(axis=1)
    return int(bad.sum())


def rank0_main(cell, family, rdv, owners, args, t_spawn):
    import numpy as np

    marks = [("start", T_PROCESS), ("owners spawned", t_spawn)]

    def mark(name):
        marks.append((name, time.perf_counter()))

    store, ds = open_store(cell, family, rdv, 0, args.seed)
    store_up_s = time.perf_counter() - t_spawn
    mark("store up")
    say(f"store up: {cell.traffic['ranks']} rank(s) over "
        f"{cell.traffic['backend']}, {len(ds)} rows, in {store_up_s:.1f} s")

    devs = init_device(cell)
    import jax

    from ddstore_tpu.data import DeviceLoader, DistributedSampler
    from ddstore_tpu.parallel import make_mesh

    lowered = []

    def on_event(event, secs, **kw):
        if event == LOWERED_EVENT:
            lowered.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    mark("device up")
    used = devs[:cell.chips]
    mesh = make_mesh(dict(cell.traffic["mesh"]), used)
    job = family.build(cell.config, cell.traffic, mesh, args.seed,
                       cell.dry_run)
    mark("state built")
    batch = job.batch
    sampler = DistributedSampler(len(ds), store.world, store.rank,
                                 seed=args.seed)

    def new_loader(epoch):
        sampler.set_epoch(epoch)
        return DeviceLoader(ds, sampler, batch_size=batch, mesh=mesh,
                            **job.loader_kwargs)

    # -- set-up: epoch 0's first batches against the reference, the first
    # step against the plain forward pass, and the warm-up.
    sampler.set_epoch(0)
    ids = np.fromiter(itertools.islice(iter(sampler), CHECK_FIRST * batch),
                      np.int64)
    want = family.reference_rows(args.seed, ids, cell.traffic, cell.config)
    want = want if isinstance(want, tuple) else (want,)
    checked = differed = 0
    call_s, ref_loss, first_loss = [], None, None
    it = iter(new_loader(0))
    for i in range(max(CHECK_FIRST, int(cell.traffic["warmup_steps"]))):
        b = next(it)
        if i < CHECK_FIRST:
            host = jax.tree_util.tree_map(np.asarray, b)
            differed += rows_differing(
                host, tuple(w[i * batch:(i + 1) * batch] for w in want))
            checked += batch
            if i == 0:
                t0 = time.perf_counter()
                ref_loss = job.reference_loss(host)
                say(f"reference forward pass: loss {ref_loss:.6f} in "
                    f"{time.perf_counter() - t0:.1f} s (compile included)")
                mark("rows and reference checked")
        t0 = time.perf_counter()
        loss = jax.block_until_ready(job.step(b))
        call_s.append(time.perf_counter() - t0)
        if i == 0:
            first_loss = float(loss)
    it.close()
    mark("warmed up")
    say("set-up: " + ", ".join(
        f"{name} +{t - marks[i][1]:.1f} s"
        for i, (name, t) in enumerate(marks[1:])))
    # A family that compiles ahead of its first call says how long that
    # took; otherwise it is the first call, completed, less the second.
    compile_s = getattr(job, "compile_s", call_s[0] - call_s[1])
    loss_err = abs(first_loss - ref_loss) / abs(ref_loss)
    say(f"first step: loss {first_loss:.6f}, reference {ref_loss:.6f}, "
        f"relative difference {loss_err:.2e} (allowed "
        f"{cell.config['loss_rtol']}); trace+lower+compile "
        f"{compile_s:.1f} s; {checked} delivered rows checked, "
        f"{differed} differ")
    check_owners(owners)

    # -- the window.
    spans = Spans()
    tracer = Tracer(cell.traffic["trace"]) if args.trace else None
    pending, losses = collections.deque(), []
    n_lowered0 = len(lowered)
    steps, epoch, stop = 0, 1, False
    setup_s = time.perf_counter() - T_PROCESS
    t0 = time.perf_counter()
    while not stop:
        it = iter(new_loader(epoch))
        while True:
            with spans("next_batch"):
                b = next(it, None)
            if b is None:
                break
            with spans("dispatch"):
                pending.append(job.step(b))
            steps += 1
            if len(pending) > LAG:
                with spans("wait_step"):
                    losses.append(jax.block_until_ready(pending.popleft()))
            since = time.perf_counter() - t0
            if tracer is not None:
                tracer.tick(since, steps)
            if since >= args.seconds:
                stop = True
                break
        it.close()
        epoch += 1
    with spans("wait_step"):
        losses.extend(jax.block_until_ready(loss) for loss in pending)
    window_s = time.perf_counter() - t0
    if tracer is not None:
        tracer.finish(steps)
    lowered_in_window = len(lowered) - n_lowered0
    check_owners(owners)

    # -- after the window: the store against the reference once more.
    rng = np.random.default_rng((args.seed, 3))
    for _ in range(CHECK_AFTER):
        ids = rng.integers(0, len(ds), batch)
        differed += rows_differing(
            ds.fetch(ids),
            family.reference_rows(args.seed, ids, cell.traffic, cell.config))
        checked += batch
    giveups = int(store.fault_stats()["retry_giveups"])
    finite = all(math.isfinite(float(x)) for x in losses)
    failed = differed + giveups
    correct = (failed == 0 and finite and lowered_in_window == 0
               and loss_err <= float(cell.config["loss_rtol"]))
    say(f"window: {steps} steps in {window_s:.3f} s over {epoch - 1} "
        f"epoch(s); losses finite: {finite}; programs lowered inside the "
        f"window: {lowered_in_window}; rows checked {checked}, differing "
        f"{differed}; give-ups {giveups}")

    trace = tracer.reduce(args.keep_trace) if tracer is not None else None
    ctx = {
        "cell": cell, "job": job, "unit": family.UNIT, "chips": cell.chips,
        "device_kind": used[0].device_kind, "window_s": window_s,
        "steps": steps, "units": steps * batch * job.units_per_row,
        "span_s": dict(spans.seconds), "setup_s": setup_s,
        "compile_s": compile_s, "store_up_s": store_up_s, "trace": trace,
        "traced_steps": tracer.steps if tracer is not None else 0,
    }
    group = "per_layer" if args.trace else "end_to_end"
    readings = {}
    for name in cell.metric_names(group):
        try:
            value = spec.load_module("metrics", name).read(ctx)
        except KeyError as e:
            # peaks.json refuses a device it does not know. On the chip that
            # is an error; a dry run's CPU has no peak and reports nothing.
            if not cell.dry_run:
                raise
            say(f"dry run: {name} left out ({e})")
            continue
        if value is not None:
            readings[name] = {"value": float(value),
                              "unit": cell.metric_unit(name)}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devs)}
    stats = [d.memory_stats() for d in used]
    say(f"memory_stats of chip 0: {stats[0]}")
    if all(s and "peak_bytes_in_use" in s for s in stats):
        # The runtime counts live arrays (state, batches) as "in use" and the
        # temporaries of running programs, most of a training step, as
        # "reserved"; the chip holds both at once.
        device["memory_peak_bytes"] = max(
            int(s["peak_bytes_in_use"]) + int(s.get("peak_bytes_reserved", 0))
            for s in stats)
    result = {"correct": bool(correct),
              "attempted": steps * batch + checked, "failed": failed}
    if cell.dry_run:
        say("dry-run readings (a CPU's, not metrics): "
            + json.dumps({k: v["value"] for k, v in readings.items()}))
        result.update(metrics={}, device=device, dry_run=True)
    else:
        if trace is not None:
            device.update(busy_s=trace.busy_s(), window_s=trace.window_s)
            result["breakdown"] = trace.breakdown()
        result.update(metrics=readings, device=device)
    if tracer is not None:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    with open(os.path.join(rdv, "bench.done"), "w"):
        pass
    store.close()
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="toy sizes on the CPU; never selected by detection, "
                         "never reports a metric")
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="with --trace 1, also copy the .xplane.pb here")
    ap.add_argument("--owner", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--rdv", help=argparse.SUPPRESS)
    args = ap.parse_args()

    bench = spec.load_benchmark()
    cell = spec.Cell(bench, args.workload, args.dry_run)
    family = cell.family()
    os.environ.update(cell.traffic.get("env", {}))
    if args.owner is not None:
        owner_main(cell, family, args.rdv, args.owner, args.seed)
        return
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    if args.dry_run:
        say("DRY RUN (cpu)")
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={cell.chips}")
    mem_gb = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE") / 1e9
    say(f"cell {cell.name}: config {cell.entry['config']} "
        f"({cell.family_name}), traffic {cell.entry['traffic']}, "
        f"{cell.chips} chip(s); host has {os.cpu_count()} cores, "
        f"{mem_gb:.0f} GB")

    # Build the native core once, here, so the owners do not race to.
    from ddstore_tpu import _build

    t0 = time.perf_counter()
    _build.build()
    say(f"native core ready in {time.perf_counter() - t0:.1f} s")

    rdv = tempfile.mkdtemp(prefix="bench_rdv_")
    # Data-only owners: JAX_PLATFORMS=cpu keeps any stray import off the chip.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.abspath(__file__), "--workload", cell.name,
           "--seed", str(args.seed), "--rdv", rdv]
    if args.dry_run:
        cmd.append("--dry-run")
    t_spawn = time.perf_counter()
    owners = [subprocess.Popen(cmd + ["--owner", str(r)], env=env)
              for r in range(1, int(cell.traffic["ranks"]))]
    try:
        result = rank0_main(cell, family, rdv, owners, args, t_spawn)
        for r, p in enumerate(owners, start=1):
            if p.wait(timeout=60) != 0:
                raise RuntimeError(f"owner {r} exited with {p.returncode}")
    finally:
        for p in owners:
            if p.poll() is None:
                p.kill()
            p.wait()
        shutil.rmtree(rdv, ignore_errors=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
