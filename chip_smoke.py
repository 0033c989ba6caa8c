"""The quickest proof that the store-fed training path still starts on the chip.

    python chip_smoke.py            # on a machine with a TPU; fails without one
    python chip_smoke.py --dry-run  # toy sizes on the CPU, for the sandbox

One process — this one — owns the chip(s). Before it touches JAX it starts
three data-only owner processes (ranks 1..3 of a 4-rank store over the TCP
backend, default routing) that register their shards, serve reads and
never build a mesh. Rank 0 then drives, through the API the examples use
(FileGroup / DDStore / ShardedDataset / DistributedSampler / DeviceLoader,
create_train_state / make_train_step):

* store leg   — the VAE at its real width (784-400-20, uint8 rows, global
                batch 512, loader defaults) fed over the wire;
* kernel leg  — the LM at the widest it has been run (vocab 32768, d1024,
                16 heads of 64, 8 layers, bf16), token windows from the
                same store, the lowered step checked for the Mosaic
                kernels, then flash against the XLA reference (outputs and
                gradients) on this backend;
* experts leg — one chip only: the three described expert models at their
                published widths (latent attention: dense layer + one
                expert layer + MTP; short convolutions and grouped-query
                attention: the five-layer pattern; Mamba-2, rotary-free
                attention and ungated experts: the nine one-branch layers;
                S=2048), loss and every gradient leaf against the plain
                float32 reference, and the tokens the two route
                differently; the same again with every pair routed to the
                held experts (every trip of the routed part's loop, none
                dropped); then the chunked state-space scan and the biased
                convolution's kernels alone, outputs and gradients, against
                the recurrence walked a position at a time and the plain
                shifted products;
* ragged leg  — one short epoch of the examples/gnn_molecules.py path;
* two timings the next issues need, labelled as smoke output.

With more than one device the store and ragged legs run data-parallel over
all of them, the LM runs at S=8192 on dp=2 x sp=2 (ring attention around
the flash kernel), and both are compared against a one-device run of the
same seed; the one-device-only checks are left to the one-chip run.

Any failure raises: there is no try/except around a leg and no leg is
skipped with exit 0. The last stdout line is the result JSON; it is
printed only after every leg passed.
"""

import argparse
import contextlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

WORLD = 4
SEED = 0

# (seq, global batch, steps) per LM run. REAL is the contract; DRY only has
# to reach the same code on the CPU in seconds.
REAL = dict(dry_run=False, vae_rows=16384, vae_batch=512,
            lm=dict(vocab=32768, dim=1024, heads=16, layers=8),
            lm_runs=((2048, 8, 3), (8192, 2, 2)),
            attn_s=2048, attn_s_misaligned=1032,
            # (batch, seq); (loss, gradient leaf) tolerances of bf16 against
            # float32; share of tokens a near-tie may route differently in
            # a layer, a configuration: it grows with depth as bf16 and
            # float32 hidden states drift apart. glm47's two expert layers
            # read 2.3 % and 2.5 %; the LFM2 pattern's four, 4 of 32 experts
            # a token, 2.3 %, 3.7 %, 5.5 % and 6.2 % (chip, PR 31)
            experts_shape=(1, 2048), experts_tol=(1e-3, 0.3),
            experts_flips={"glm47-flash-ep8": 0.05, "lfm2-8b-a1b-ep4": 0.10,
                           "nemotron3-nano-ep16": 0.20},
            # the scan alone: (S, heads, head width, groups, state, chunk),
            # the published widths
            ssd_shape=(2048, 64, 64, 8, 128, 128), ssd_tol=3e-2,
            graphs=256, chain_steps=5, stage_reps=20)
DRY = dict(dry_run=True, vae_rows=2048, vae_batch=64,
           lm=dict(vocab=512, dim=64, heads=4, layers=2),
           lm_runs=((128, 4, 3), (256, 2, 2)),
           attn_s=128, attn_s_misaligned=136,
           experts_shape=(1, 64), experts_tol=(1e-5, 1e-3),
           experts_flips={"glm47-flash-ep8": 0.0, "lfm2-8b-a1b-ep4": 0.0,
                          "nemotron3-nano-ep16": 0.0},
           ssd_shape=(64, 4, 8, 2, 16, 16), ssd_tol=1e-4,
           graphs=64, chain_steps=3, stage_reps=5)
# Steps of the one-device VAE run a multi-device run is compared against.
VAE_REF_STEPS = 5


def say(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# The store: every rank runs this, in this order (registration is collective).
# ---------------------------------------------------------------------------


def token_windows(rank, seq, n, vocab):
    """The lm_longcontext.py corpus: a repeated 64-token pattern, learnable
    in a few steps. Returns (tokens, next-tokens), both (n, seq) int32."""
    import numpy as np

    rng = np.random.default_rng((SEED, rank, seq))
    base = rng.integers(0, vocab, size=64)
    corpus = np.tile(base, n * seq // 64 + 2)
    starts = rng.integers(0, len(corpus) - seq - 1, size=n)
    win = np.stack([corpus[s:s + seq] for s in starts]).astype(np.int32)
    nxt = np.stack([corpus[s + 1:s + seq + 1] for s in starts]
                   ).astype(np.int32)
    return win, nxt


def open_store(rdv, rank, cfg):
    """Join the 4-rank store and register this rank's shard of every
    dataset the legs read. Host-only: no JAX backend is touched."""
    import numpy as np

    from ddstore_tpu import DDStore, FileGroup
    from ddstore_tpu.data import (GraphShardedDataset, ShardedDataset,
                                  synthetic_graphs, synthetic_mnist)

    store = DDStore(FileGroup(rdv, rank, WORLD), backend="tcp")
    pixels, _ = synthetic_mnist(WORLD * cfg["vae_rows"], SEED)
    sets = {"pixels": pixels, "vae": ShardedDataset(store, pixels,
                                                    name="vae")}
    for seq, batch, steps in cfg["lm_runs"]:
        win, nxt = token_windows(rank, seq, batch * steps,
                                 cfg["lm"]["vocab"])
        sets[f"lm{seq}"] = ShardedDataset(store, win, nxt, name=f"lm{seq}",
                                          pre_sharded=True)
    graphs = synthetic_graphs(np.random.default_rng(SEED + rank),
                              cfg["graphs"])
    sets["gnn"] = GraphShardedDataset(store, graphs, graphs_per_slot=8)
    return store, sets


def owner_main(rdv, rank, cfg):
    """Ranks 1..3: register, serve until rank 0 says done (or dies), close."""
    parent = os.getppid()
    store, _ = open_store(rdv, rank, cfg)
    done = os.path.join(rdv, "smoke.done")
    while not os.path.exists(done):
        if os.getppid() != parent:
            sys.exit(f"owner {rank}: rank 0 is gone")
        time.sleep(0.05)
    store.close()


# ---------------------------------------------------------------------------
# Rank 0: the legs.
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def leg(name, record):
    """Times one leg and prints its line. Does not catch: a failing leg
    propagates and the process exits non-zero."""
    say(f"--- leg {name}")
    entry = record.setdefault(name, {"compile_s": 0.0})
    t0 = time.perf_counter()
    yield entry
    entry["wall_s"] = round(time.perf_counter() - t0, 2)
    entry["compile_s"] = round(entry["compile_s"], 2)
    say(f"--- leg {name} ok: wall {entry['wall_s']} s, of which compile "
        f"{entry['compile_s']} s")


def compile_step(step, entry, *args):
    """Lower and compile ``step`` for ``args`` ahead of its first call so
    compile seconds are reported apart from run seconds."""
    t0 = time.perf_counter()
    lowered = step.lower(*args)
    compiled = lowered.compile()
    entry["compile_s"] += time.perf_counter() - t0
    return lowered, compiled


def assert_on_every_device(mesh, what, tree, cfg):
    """Every mesh device holds a shard of every leaf of ``tree`` and (on a
    real accelerator) reports memory in use."""
    import jax

    want = set(mesh.devices.flat)
    for leaf in jax.tree_util.tree_leaves(tree):
        have = {s.device for s in leaf.addressable_shards}
        if have != want:
            raise AssertionError(f"{what}: shards on {sorted(map(str, have))}"
                                 f", mesh is {sorted(map(str, want))}")
    if not cfg["dry_run"]:
        for d in want:
            if not d.memory_stats()["bytes_in_use"] > 0:
                raise AssertionError(f"{what}: {d} reports no bytes in use")


def assert_finite(name, losses):
    import math

    if not losses or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{name}: non-finite loss in {losses}")


def assert_close(name, got, want, rtol):
    for i, (g, w) in enumerate(zip(got, want)):
        if abs(g - w) > rtol * abs(w):
            raise AssertionError(
                f"{name}: step {i} loss {g} vs one-device {w} "
                f"(rtol {rtol})")
    say(f"    {name}: losses agree with the one-device run within "
        f"{rtol:g}: {[round(g, 4) for g in got]} vs "
        f"{[round(w, 4) for w in want]}")


def run_vae(store, ds, mesh, cfg, entry, steps):
    import jax

    from ddstore_tpu.data import DeviceLoader, DistributedSampler
    from ddstore_tpu.models import vae

    model, state, tx = vae.create_train_state(jax.random.key(SEED),
                                              mesh=mesh)
    step = vae.make_train_step(model, tx, mesh=mesh)
    sampler = DistributedSampler(len(ds), store.world, store.rank, seed=SEED)
    sampler.set_epoch(0)
    loader = DeviceLoader(ds, sampler, batch_size=cfg["vae_batch"],
                          mesh=mesh)
    key = jax.random.key(SEED + 1)
    losses, compiled = [], None
    for i, xb in enumerate(loader):
        if i >= steps:
            break
        key, sub = jax.random.split(key)
        if compiled is None:
            _, compiled = compile_step(step, entry, state, xb, sub)
            assert_on_every_device(mesh, "vae batch", xb, cfg)
        state, loss = compiled(state, xb, sub)
        losses.append(float(loss) / cfg["vae_batch"])
    assert_on_every_device(mesh, "vae state", state, cfg)
    assert_finite("vae", losses)
    return losses, loader


def store_leg(store, sets, mesh, mesh1, cfg, record):
    import numpy as np

    from ddstore_tpu import diag

    ds, pixels = sets["vae"], sets["pixels"]
    with leg("store", record) as entry:
        # One batch byte-for-byte against the seeded generator. The rows
        # are a permutation over all four shards, so 3/4 of them are remote.
        idx = np.random.default_rng(SEED).permutation(len(ds))[
            :cfg["vae_batch"]]
        got = ds.fetch(idx)
        if got.dtype != np.uint8 or not np.array_equal(got, pixels[idx]):
            raise AssertionError("fetched batch differs from the generator")
        owners = np.bincount(store.owner_of_rows(ds.data_var, idx),
                             minlength=WORLD)
        say(f"    byte-exact batch of {len(idx)} uint8 rows, rows per "
            f"owner {owners.tolist()}")

        steps = len(ds) // WORLD // cfg["vae_batch"]
        losses, loader = run_vae(store, ds, mesh, cfg, entry, steps)
        head, tail = sum(losses[:3]) / 3, sum(losses[-3:]) / 3
        say(f"    vae {steps} steps on {mesh.devices.size} device(s): "
            f"loss/sample {head:.2f} -> {tail:.2f}")
        if steps < 20 or not tail < head:
            raise AssertionError(f"vae: {steps} steps, loss {head} -> "
                                 f"{tail} did not fall")
        if mesh.devices.size > 1:
            ref, _ = run_vae(store, ds, mesh1, cfg, entry, VAE_REF_STEPS)
            assert_close("vae dp", losses[:len(ref)], ref, 1e-3)

        faults = store.fault_stats()
        if faults["retry_giveups"]:
            raise AssertionError(f"store gave up on reads: {faults}")
        wire = int(sum(store.lane_bytes()))
        cma = int(store.cma_ops)
        route = "CMA" if cma and not wire else "TCP" if not cma else \
            "CMA and TCP"
        say(f"    remote rows served by: {route} (cma_ops={cma}, "
            f"tcp_wire_bytes={wire}, wire="
            f"{store.transport_facts()['wire']}); give-ups 0, retries "
            f"{faults['retry_attempts']}")
        m = loader.metrics.summary()
        say(f"    loader (host clock): fetch p50 "
            f"{m['host_fetch']['p50_s'] * 1e3:.2f} ms, stage enqueue p50 "
            f"{m['stage_enqueue']['p50_s'] * 1e3:.2f} ms, consumer wait p50 "
            f"{m['device_wait']['p50_s'] * 1e3:.2f} ms")
        say("    python -m ddstore_tpu.diag:")
        diag.main([])
    return route


def run_lm(store, ds, mesh, cfg, entry, seq, batch, steps):
    """``steps`` donated LM steps at (seq, batch) fed by the store; returns
    (losses, lowered text, compiled step, state, last batch)."""
    import jax
    import jax.numpy as jnp

    from ddstore_tpu.data import DeviceLoader, DistributedSampler
    from ddstore_tpu.models import transformer

    sp = mesh.shape.get("sp", 1)
    # f32 on the CPU: XLA's CPU backend cannot promote the bf16 all-reduce
    # the sp mesh produces (see examples/lm_longcontext.py).
    dtype = jnp.float32 if cfg["dry_run"] else jnp.bfloat16
    model = transformer.TransformerLM(compute_dtype=dtype, mesh=mesh,
                                      **cfg["lm"])
    state, tx = transformer.create_train_state(jax.random.key(SEED), model,
                                               lr=1e-3, mesh=mesh)
    step = transformer.make_train_step(model, tx, mesh=mesh, state=state)
    sampler = DistributedSampler(len(ds), store.world, store.rank, seed=SEED)
    sampler.set_epoch(0)
    loader = DeviceLoader(ds, sampler, batch_size=batch, mesh=mesh,
                          spec=jax.P("dp", "sp" if sp > 1 else None))
    pos = jnp.tile(jnp.arange(seq, dtype=jnp.int32), (batch, 1))
    losses, lowered, compiled = [], None, None
    for tok, tgt in loader:
        if compiled is None:
            lowered, compiled = compile_step(step, entry, state, tok, tgt,
                                             pos)
            assert_on_every_device(mesh, f"lm S={seq} batch", (tok, tgt),
                                   cfg)
        state, loss = compiled(state, tok, tgt, pos)
        losses.append(float(loss))
    if len(losses) != steps:
        raise AssertionError(f"lm S={seq}: {len(losses)} steps, want "
                             f"{steps}")
    assert_on_every_device(mesh, f"lm S={seq} state", state, cfg)
    assert_finite(f"lm S={seq}", losses)
    say(f"    lm S={seq} b={batch} on {dict(mesh.shape)}: losses "
        f"{[round(x, 4) for x in losses]}")
    return losses, lowered.as_text(), compiled, state, (tok, tgt, pos)


def assert_mosaic(text, cfg, exact):
    """The lowered step holds the Mosaic flash kernels (fwd, dq, dkv: three
    custom calls per layer), i.e. attention did not lower to the XLA
    reference. Under ring attention each ring step carries its own."""
    n, layers = text.count("tpu_custom_call"), cfg["lm"]["layers"]
    if cfg["dry_run"]:
        say(f"    lowered step: {n} tpu_custom_call (CPU dry run lowers "
            f"the XLA reference; not asserted)")
        return
    if n < 3 * layers or (exact and n != 3 * layers):
        raise AssertionError(f"lowered LM step has {n} tpu_custom_call, "
                             f"want {'' if exact else '>= '}{3 * layers}")
    say(f"    lowered step: {n} tpu_custom_call over {layers} layers "
        f"(Mosaic flash fwd + dq + dkv)")


def kernel_leg(store, sets, mesh1, cfg, record):
    import jax

    from ddstore_tpu.ops.attention_check import flash_reference_check

    facts = {}
    with leg("kernel", record) as entry:
        for seq, batch, steps in cfg["lm_runs"]:
            _, text, compiled, state, args = run_lm(
                store, sets[f"lm{seq}"], mesh1, cfg, entry, seq, batch,
                steps)
            assert_mosaic(text, cfg, True)
            if not facts:
                facts = chained_steps_fact(compiled, state, args,
                                           cfg["chain_steps"])
            # Free this run's state and program before the next one is
            # built: the two do not fit the chip side by side.
            del compiled, state, args
        t0 = time.perf_counter()
        n = flash_reference_check(cfg["attn_s"], cfg["attn_s_misaligned"])
        say(f"    flash == reference on {jax.default_backend()}: {n} cases "
            f"(outputs and gradients, hd 64 and 128, causal, both ring "
            f"offsets, sequence-major at hd 256 and at hd 128 on grouped "
            f"K/V, S={cfg['attn_s_misaligned']} not a multiple of 16, "
            f"cond-of-kernels) in {time.perf_counter() - t0:.1f} s "
            f"including their compiles")
    return facts


def kernel_leg_multichip(store, sets, mesh, mesh1, cfg, record):
    seq, batch, steps = cfg["lm_runs"][-1]  # the long-sequence run
    ds = sets[f"lm{seq}"]
    with leg("kernel (dp=2 x sp=2)", record) as entry:
        got, text, *_ = run_lm(store, ds, mesh, cfg, entry, seq, batch,
                               steps)
        assert_mosaic(text, cfg, False)
        want, *_ = run_lm(store, ds, mesh1, cfg, entry, seq, batch, steps)
        # bf16 activations, a different reduction order in the ring and in
        # the gradient all-reduce (f32 in the dry run).
        assert_close("lm dp x sp", got, want,
                     1e-5 if cfg["dry_run"] else 2e-2)


def experts_leg(cfg, record):
    """The three described expert architectures at their published widths,
    each the bf16 program against its plain float32 reference: loss, every
    gradient leaf, and how many tokens the two route differently (near-ties
    of the sigmoid scores); then the same with every pair routed to the
    held experts. ``glm47-flash-ep8`` cut to its dense layer, one expert
    layer and the MTP module; ``lfm2-8b-a1b-ep4`` at its five-layer pattern
    (a dense conv layer, attention and three conv layers with experts);
    ``nemotron3-nano-ep16`` at its nine one-branch layers (four Mamba-2,
    four of ungated experts, one of rotary-free attention); then that
    configuration's scan and convolution alone; then ``sdar-30b-a3b-ep8``
    cut to two layers, a first step's loss and gradient under the
    block-diffusion mask."""
    with leg("experts", record):
        _against_reference(cfg, "glm47-flash-ep8", "mla_moe_lm", layers=2)
        _against_reference(cfg, "lfm2-8b-a1b-ep4", "lfm2_moe_lm")
        _against_reference(cfg, "nemotron3-nano-ep16", "nemotron_h_lm")
        _scan_against_recurrence(cfg)
        _diffusion_against_reference(cfg)


def _reference_module(reference):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "smoke_ref_" + reference, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmarks",
            "reference", reference + ".py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


def _scan_against_recurrence(cfg):
    """``ops/ssd.py`` (chunked, bf16 operands on the chip) against the
    reference's recurrence walked a position at a time in float32, and
    ``ops/short_conv.py``'s biased convolution kernels against plain
    shifted products: outputs and every gradient, by the norm of the
    difference over the reference's norm."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddstore_tpu.ops.short_conv import short_conv
    from ddstore_tpu.ops.ssd import ssd

    ref = _reference_module("nemotron_h_lm")
    s, h, p, g, n, chunk = cfg["ssd_shape"]
    dtype = jnp.float32 if cfg["dry_run"] else jnp.bfloat16
    rng = np.random.default_rng((SEED, 33))
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, B, C = normal(1, s, h, p), normal(1, s, g, n), normal(1, s, g, n)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                        (1, s, h))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    D, dy = normal(h), normal(1, s, h, p)
    low = lambda t: t.astype(dtype)
    # both are given what the program's operands round to
    args = (low(x), dt, A, low(B), low(C), D)

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a).astype(jnp.float32) * dy).sum(),
            argnums=range(6)))(*args)

    def worst(got, want):
        norm = lambda t: float(jnp.linalg.norm(t.astype(jnp.float32)))
        return max(norm(a - b.astype(a.dtype)) / norm(b)
                   for a, b in zip(jax.tree_util.tree_leaves(got),
                                   jax.tree_util.tree_leaves(want)))

    with jax.default_matmul_precision("highest"):
        want = run(lambda x, dt, A, B, C, D: ref.scan_recurrence(
            *(t.astype(jnp.float32) for t in (x, dt, A, B, C, D))))
    err = worst(run(lambda *a: ssd(*a, chunk)), want)
    say(f"    ssd S={s} H={h} P={p} G={g} N={n} chunk {chunk} in "
        f"{jnp.dtype(dtype).name} == the recurrence in float32: output "
        f"sum and six gradients, worst relative norm of the difference "
        f"{err:.2e}")
    if not err <= cfg["ssd_tol"]:
        raise AssertionError(f"ssd != the recurrence: {err:.2e} (allowed "
                             f"{cfg['ssd_tol']})")
    c = h * p + 2 * g * n
    xc, taps, bias, dyc = normal(1, s, c), normal(4, c), normal(c), \
        normal(1, s, c)

    def plain(x, taps, bias):
        z = jnp.pad(x.astype(jnp.float32), ((0, 0), (3, 0), (0, 0)))
        return jax.nn.silu(bias + sum(
            taps[j] * jax.lax.slice_in_dim(z, j, j + s, axis=1)
            for j in range(4)))

    conv = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * dyc).sum(),
        argnums=(0, 1, 2)))(low(xc), taps, bias)
    err = worst(conv(short_conv), conv(plain))
    say(f"    conv + bias + silu kernels over {c} channels == shifted "
        f"products: worst relative norm of the difference {err:.2e}")
    if not err <= cfg["ssd_tol"]:
        raise AssertionError(f"short_conv != shifted products: {err:.2e} "
                             f"(allowed {cfg['ssd_tol']})")


def _against_reference(cfg, config, reference, layers=None):
    """One configuration of ``benchmarks/configs`` (``layers``: cut to that
    depth) against ``benchmarks/reference/<reference>.py``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddstore_tpu.models import transformer

    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "benchmarks", "configs",
                           config + ".json")) as f:
        desc = json.load(f)
    if layers is not None:
        desc["num_hidden_layers"] = layers
    if cfg["dry_run"]:
        desc.update(desc["dry_run"])
    ref = _reference_module(reference)
    batch, seq = cfg["experts_shape"]
    dtype = jnp.float32 if cfg["dry_run"] else jnp.bfloat16
    model = transformer.lm_from_description(desc, compute_dtype=dtype)
    # the parameters alone: Adam's moments (two more trees) would not leave
    # the float32 reference's gradient program its 6.6 GB on the chip
    params = transformer.create_train_state(jax.random.key(SEED),
                                            model)[0].params
    rng = np.random.default_rng((SEED, 27))
    tok, tgt = rng.integers(0, model.vocab, (2, batch, seq), dtype=np.int32)
    pos = np.tile(np.arange(seq, dtype=np.int32), (batch, 1))
    mine = jax.jit(jax.value_and_grad(
        lambda p: transformer.lm_loss(model, p, tok, tgt, pos),
        has_aux=True))
    arch = dict(model.arch._asdict(), heads=model.heads)
    theirs = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tok, tgt, pos, arch=arch, token_block=1024)))
    norm = lambda t: float(jnp.linalg.norm(t.astype(jnp.float32)))

    def against_reference(params):
        """Loss, its relative error, each gradient leaf's relative error
        and the load vectors, on ``params``."""
        (loss, loads), grads = mine(params)
        want, want_grads = theirs(params)
        rel = {}
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(grads)[0],
                jax.tree_util.tree_leaves(want_grads)):
            if norm(w) > 0:
                rel[jax.tree_util.keystr(path)] = norm(g - w) / norm(w)
            elif norm(g) > 0:
                raise AssertionError(f"{path}: gradient where the "
                                     f"reference has none")
        return (float(loss), float(want),
                abs(float(loss) - float(want)) / abs(float(want)), rel,
                np.asarray(loads))

    loss, want, loss_err, rel, loads = against_reference(params)
    worst = max(rel, key=rel.get)
    # Who is routed differently: the program's choice (sown by the expert
    # layers, in the order of the loads) against the reference's, expert
    # sets a token.
    _, inter = model.clone(remat=False).apply(
        params, tok, pos, True, next_tokens=tgt, mutable=["intermediates"])
    chose = []
    for path in transformer._expert_layers(model):
        node = inter["intermediates"]
        for key in path:
            node = node[key]
        chose.append(node["chosen"][0])
    they_chose = jax.jit(lambda p: ref.forward(
        p, tok, tgt, pos, arch, token_block=1024)[1])(params)
    differ = [int((np.sort(np.asarray(a), -1)
                   != np.sort(np.asarray(b), -1)).any(-1).sum())
              for a, b in zip(chose, they_chose)]
    a = model.arch
    which, of = a.expert_share
    held = a.n_routed_experts // of
    mine_held = slice(which * held, (which + 1) * held)
    say(f"    {config} b={batch} S={seq}: loss {loss:.6f}, "
        f"reference {want:.6f} (relative {loss_err:.2e}); "
        f"gradient leaves {len(rel)}, relative norm of the difference "
        f"median {sorted(rel.values())[len(rel) // 2]:.2e}, worst "
        f"{rel[worst]:.2e} at {worst}; tokens routed differently "
        f"{differ} of {batch * seq} a layer; held experts' load "
        f"{loads[:, mine_held].tolist()}")
    rtol, gtol = cfg["experts_tol"]
    if loss_err > rtol or rel[worst] > gtol \
            or max(differ) > cfg["experts_flips"][config] * batch * seq:
        raise AssertionError(
            f"experts leg, {config}: loss {loss_err:.2e} (allowed {rtol}), "
            f"worst gradient leaf {rel[worst]:.2e} at {worst} (allowed "
            f"{gtol}), routed differently {differ}")
    # Once more with every router's bias sending every pair to the experts
    # held here: all T x k sorted rows are live, so the routed part's loop
    # makes every trip (four of 2,048 rows at glm47's published widths),
    # where balanced routing needs the first alone. (The toy sizes hold
    # fewer experts than a token takes, and make one trip.)
    pairs = batch * seq * min(a.num_experts_per_tok, held)
    mine_only = jnp.where(
        jnp.arange(a.n_routed_experts) // held == which, 10.0, 0.0)
    crowded = jax.tree_util.tree_map_with_path(
        lambda path, leaf: mine_only if "router_bias"
        in jax.tree_util.keystr(path) else leaf, params)
    loss, want, loss_err, rel, loads = against_reference(crowded)
    worst = max(rel, key=rel.get)
    live = loads[:, mine_held].sum(-1)
    say(f"    every pair on the held experts: loss {loss:.6f}, "
        f"reference {want:.6f} (relative {loss_err:.2e}); worst "
        f"gradient leaf {rel[worst]:.2e} at {worst}; held pairs a layer "
        f"{live.tolist()} of {pairs}")
    if loss_err > rtol or rel[worst] > gtol or (live != pairs).any():
        raise AssertionError(
            f"experts leg, {config}, every pair held: loss {loss_err:.2e} "
            f"(allowed {rtol}), worst gradient leaf {rel[worst]:.2e} at "
            f"{worst} (allowed {gtol}), held pairs {live.tolist()}")


def _diffusion_against_reference(cfg, config="sdar-30b-a3b-ep8", layers=2):
    """The block-diffusion configuration cut to ``layers``, a first step
    under the mask: the bf16 program's loss and every gradient leaf, for the
    draw of step 0, against ``benchmarks/reference/sdar_moe_lm.py`` given
    the same draw. On the chip the flash kernels run the mask; the
    reference builds it dense from its rules."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddstore_tpu.models import transformer

    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "benchmarks", "configs",
                           config + ".json")) as f:
        desc = json.load(f)
    desc["num_hidden_layers"] = layers
    if cfg["dry_run"]:
        desc.update(desc["dry_run"])
    ref = _reference_module("sdar_moe_lm")
    batch, seq = cfg["experts_shape"]
    dtype = jnp.float32 if cfg["dry_run"] else jnp.bfloat16
    model = transformer.lm_from_description(desc, compute_dtype=dtype)
    params = transformer.create_train_state(jax.random.key(SEED),
                                            model)[0].params
    a = model.arch
    # ids below the mask token
    tok = np.random.default_rng((SEED, 37)).integers(
        0, a.mask_token, (batch, seq), dtype=np.int32)
    pos = np.tile(np.arange(seq, dtype=np.int32), (batch, 1))
    key = transformer.diffusion_key(a, 0)
    masked, t = transformer.diffusion_noise(a, key, batch, seq)
    (loss, loads), grads = jax.jit(jax.value_and_grad(
        lambda p: transformer.lm_loss(model, p, tok, None, pos,
                                      noise_key=key), has_aux=True))(params)
    arch = dict(a._asdict(), heads=model.heads)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(
        p, tok, masked, t, pos, arch=arch, token_block=1024)))(params)
    norm = lambda x: float(jnp.linalg.norm(x.astype(jnp.float32)))
    rel = {jax.tree_util.keystr(path): norm(g - w) / norm(w)
           for (path, g), w in zip(
               jax.tree_util.tree_flatten_with_path(grads)[0],
               jax.tree_util.tree_leaves(want_grads)) if norm(w) > 0}
    worst = max(rel, key=rel.get)
    loss_err = abs(float(loss) - float(want)) / abs(float(want))
    which, of = a.expert_share
    held = a.n_routed_experts // of
    say(f"    {config} b={batch} S={seq} ({2 * seq} positions, blocks of "
        f"{a.block_length}, {int(masked.sum())} masked): loss "
        f"{float(loss):.6f}, reference {float(want):.6f} (relative "
        f"{loss_err:.2e}); gradient leaves {len(rel)}, relative norm of "
        f"the difference median {sorted(rel.values())[len(rel) // 2]:.2e}, "
        f"worst {rel[worst]:.2e} at {worst}; held experts' load "
        f"{np.asarray(loads)[:, which * held:(which + 1) * held].tolist()}")
    rtol, gtol = cfg["experts_tol"]
    if loss_err > rtol or rel[worst] > gtol:
        raise AssertionError(
            f"experts leg, {config}: loss {loss_err:.2e} (allowed {rtol}), "
            f"worst gradient leaf {rel[worst]:.2e} at {worst} (allowed "
            f"{gtol})")


def ragged_leg(store, sets, mesh, cfg, record):
    import jax
    import numpy as np

    from ddstore_tpu.data import DeviceLoader, DistributedSampler
    from ddstore_tpu.models import gnn

    ds = sets["gnn"]
    with leg("ragged", record) as entry:
        batch = mesh.devices.size * ds.graphs_per_slot
        sampler = DistributedSampler(len(ds), store.world, store.rank,
                                     seed=SEED)
        sampler.set_epoch(0)
        loader = DeviceLoader(ds, sampler, batch_size=batch, mesh=mesh)
        losses, compiled = [], None
        for gb in loader:
            if compiled is None:
                model, state, tx = gnn.create_train_state(
                    jax.random.key(SEED), jax.tree.map(np.asarray, gb),
                    lr=3e-3, mesh=mesh)
                step = gnn.make_train_step(model, tx, mesh=mesh)
                _, compiled = compile_step(step, entry, state, gb)
                assert_on_every_device(mesh, "gnn batch", gb, cfg)
            state, loss = compiled(state, gb)
            losses.append(float(loss))
        assert_finite("gnn", losses)
        say(f"    mpnn {len(losses)} steps of {batch} graphs (add_ragged -> "
            f"two-round fetch -> pack_graph_batch): loss {losses[0]:.4f} "
            f"-> {losses[-1]:.4f}")


def chained_steps_fact(compiled, state, args, n):
    """N chained donated LM steps closed by block_until_ready against the
    same closed by float(loss). Smoke output for the next issues, not a
    metric."""
    import jax

    def chain(close):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(n):
            state, loss = compiled(state, *args)
        close(loss)
        return time.perf_counter() - t0

    chain(float)  # settle
    bur = chain(jax.block_until_ready)
    flt = chain(float)
    say(f"    smoke fact (not a metric): {n} chained LM steps closed by "
        f"block_until_ready {bur * 1e3:.1f} ms, by float(loss) "
        f"{flt * 1e3:.1f} ms (ratio {bur / flt:.3f})")
    return {"chain_steps": n, "chain_block_until_ready_ms": round(bur * 1e3, 1),
            "chain_float_loss_ms": round(flt * 1e3, 1)}


def staging_fact(mesh1, cfg):
    """Host-to-device staging of one VAE batch as float32 and as uint8, the
    loader's own call, closed by block_until_ready. Smoke output."""
    import statistics

    import jax
    import numpy as np

    sh = jax.NamedSharding(mesh1, jax.P("dp"))
    rng = np.random.default_rng(SEED)
    out = {}
    for dtype in (np.float32, np.uint8):
        x = np.ascontiguousarray(
            rng.integers(0, 255, (cfg["vae_batch"], 784)).astype(dtype))
        times = []
        for _ in range(cfg["stage_reps"] + 1):
            t0 = time.perf_counter()
            jax.block_until_ready(
                jax.make_array_from_process_local_data(sh, x))
            times.append(time.perf_counter() - t0)
        times = times[1:]  # the first call pays one-time set-up
        name = np.dtype(dtype).name
        out[f"stage_{name}_median_ms"] = round(
            statistics.median(times) * 1e3, 3)
        out[f"stage_{name}_min_ms"] = round(min(times) * 1e3, 3)
        say(f"    smoke fact (not a metric): staging one "
            f"{cfg['vae_batch']}x784 {name} batch ({x.nbytes} B): median "
            f"{out[f'stage_{name}_median_ms']} ms, min "
            f"{out[f'stage_{name}_min_ms']} ms over {len(times)}")
    return out


def init_device(cfg):
    """The one place this process takes the chip."""
    import importlib.metadata as md

    import jax

    from ddstore_tpu.utils import enable_compile_cache

    # This process pins the platform itself: with JAX_PLATFORMS unset JAX
    # falls back to the CPU with a warning when the TPU cannot be
    # initialised, and a CPU run must never pass for a chip run.
    want = "cpu" if cfg["dry_run"] else "tpu"
    jax.config.update("jax_platforms", want)
    cache = enable_compile_cache()
    devs = jax.devices()
    if jax.default_backend() != want:
        raise RuntimeError(f"backend is {jax.default_backend()}, want "
                           f"{want}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']} jax={jax.__version__} "
        f"jaxlib={md.version('jaxlib')} libtpu={md.version('libtpu')} "
        f"python={sys.version.split()[0]}")
    say(f"compile cache: {cache}"
        + (" (from JAX_COMPILATION_CACHE_DIR)"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR") else ""))
    return devs, device


def check_owners(owners):
    """A dead owner fails the run here, not 300 s later at a barrier (its
    shared-memory shard can go on serving reads after it died)."""
    for rank, proc in enumerate(owners, start=1):
        if proc.poll() is not None:
            raise RuntimeError(f"owner {rank} exited with {proc.returncode} "
                               f"while rank 0 was still reading")


def rank0_main(rdv, cfg, owners):
    from ddstore_tpu.parallel import make_mesh

    record = {}
    t0 = time.perf_counter()
    store, sets = open_store(rdv, 0, cfg)
    say(f"store up: {WORLD} ranks over tcp, "
        f"{WORLD * cfg['vae_rows']} vae rows, in "
        f"{time.perf_counter() - t0:.1f} s")

    devs, device = init_device(cfg)
    n = len(devs)
    if n not in (1, 4):
        raise RuntimeError(f"chip_smoke runs on 1 or 4 devices, found {n}")
    mesh1 = make_mesh({"dp": 1}, devs[:1])
    mesh = make_mesh({"dp": n}, devs)

    facts = {"route": store_leg(store, sets, mesh, mesh1, cfg, record)}
    check_owners(owners)
    if n == 1:
        facts.update(kernel_leg(store, sets, mesh1, cfg, record))
    else:
        kernel_leg_multichip(store, sets,
                             make_mesh({"dp": 2, "sp": 2}, devs), mesh1,
                             cfg, record)
    check_owners(owners)
    if n == 1:
        experts_leg(cfg, record)
    ragged_leg(store, sets, mesh, cfg, record)
    if n == 1:
        with leg("staging fact", record):
            facts.update(staging_fact(mesh1, cfg))

    check_owners(owners)
    with open(os.path.join(rdv, "smoke.done"), "w"):
        pass
    store.close()
    return device, record, facts


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="toy sizes on the CPU (kernels in interpret mode); "
                         "never selected by detection")
    ap.add_argument("--owner", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--rdv", help=argparse.SUPPRESS)
    args = ap.parse_args()
    cfg = DRY if args.dry_run else REAL
    if args.owner is not None:
        owner_main(args.rdv, args.owner, cfg)
        return
    if args.dry_run:
        say("DRY RUN (cpu)")

    t_all = time.perf_counter()
    # Build the native core once, here, so the owners do not race to.
    from ddstore_tpu import _build

    t0 = time.perf_counter()
    _build.build()
    say(f"native core ready in {time.perf_counter() - t0:.1f} s")

    rdv = tempfile.mkdtemp(prefix="chip_smoke_rdv_")
    # The owners are data-only: they never import a model or build a mesh,
    # and JAX_PLATFORMS=cpu keeps any stray import off the chip.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.abspath(__file__), "--rdv", rdv]
    if args.dry_run:
        cmd.append("--dry-run")
    owners = [subprocess.Popen(cmd + ["--owner", str(r)], env=env)
              for r in range(1, WORLD)]
    try:
        device, record, facts = rank0_main(rdv, cfg, owners)
        for r, p in enumerate(owners, start=1):
            if p.wait(timeout=60) != 0:
                raise RuntimeError(f"owner {r} exited with {p.returncode}")
    finally:
        for p in owners:
            if p.poll() is None:
                p.kill()
            p.wait()
        shutil.rmtree(rdv, ignore_errors=True)

    compile_s = round(sum(v["compile_s"] for v in record.values()), 2)
    say("smoke summary: " + json.dumps({
        "legs": record, "compile_s_total": compile_s,
        "wall_s_total": round(time.perf_counter() - t_all, 1),
        "smoke_facts": facts, "dry_run": args.dry_run, "claim": None}))
    result = {"ok": True, "device": device}
    if args.dry_run:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
